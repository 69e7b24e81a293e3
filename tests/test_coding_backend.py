"""Coding-backend routing (ISSUE 6): kernel <-> numpy bit-identity, auto
dispatch, fused decode launches, CRC integrity end-to-end.

The "kernel" backend must be a drop-in for the byte-LUT "numpy" backend at
every layer: raw RSCode byte paths (property test), the EC DAP data path
under a full read/update/recon/repair cycle (e2e test), and the repair
loop's bit-rot healing (corruption test).
"""
import zlib

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # seeded fallback shim — see tests/_propfallback.py
    from _propfallback import given, settings
    from _propfallback import strategies as st

from repro.core import DSS, DSSParams
from repro.erasure.rs import AUTO_KERNEL_MIN_BYTES, RSCode, element_crc_ok
from repro.kernels.gf256_matmul import ops as gf_ops


def _blob(seed, size):
    return np.random.default_rng(seed).integers(0, 256, size, dtype=np.uint8).tobytes()


# ---------------------------------------------------------- property tests
@settings(max_examples=8, deadline=None)
@given(
    st.lists(st.binary(min_size=0, max_size=400), min_size=1, max_size=5),
    st.integers(2, 5),
    st.integers(0, 3),
    st.integers(0, 2**31 - 1),
)
def test_kernel_numpy_bit_identity(values, k, m, seed):
    """encode_bytes_batch / decode_bytes_batch / reconstruct_fragments are
    bit-identical across backends: ragged lengths, empty values, mixed index
    subsets, and m == 0 codes."""
    n = k + m
    c_np = RSCode(n=n, k=k, backend="numpy")
    c_kr = RSCode(n=n, k=k, backend="kernel")
    enc_np = c_np.encode_bytes_batch(values, with_crc=True)
    enc_kr = c_kr.encode_bytes_batch(values, with_crc=True)
    assert enc_np == enc_kr
    rng = np.random.default_rng(seed)
    items = []
    for frags, orig, crcs in enc_np:
        if rng.random() < 0.7:
            idxs = sorted(rng.permutation(n)[:k].tolist())  # mixed data+parity
        else:
            idxs = list(range(min(n, k + 1)))  # systematic (+1 spare)
        sub = {i: frags[i] for i in idxs}
        items.append((sub, orig, {i: crcs[i] for i in idxs}))
    assert c_np.decode_bytes_batch(items) == values
    assert c_kr.decode_bytes_batch(items) == values
    if m:
        data = rng.integers(0, 256, (k, 64), dtype=np.uint8)
        coded = c_np.encode(data)
        keep = sorted(rng.permutation(n)[:k].tolist())
        targets = [i for i in range(n) if i not in keep][:m]
        np.testing.assert_array_equal(
            c_np.reconstruct_fragments(targets, coded[keep], keep),
            c_kr.reconstruct_fragments(targets, coded[keep], keep),
        )


def test_backend_validation():
    with pytest.raises(ValueError):
        RSCode(n=6, k=4, backend="cuda")
    with pytest.raises(ValueError):
        DSS(DSSParams(coding_backend="fpga"))


# ---------------------------------------------------------- auto dispatch
def _counting(monkeypatch):
    calls = []
    real = gf_ops.gf256_matmul

    def wrapper(A, B, **kw):
        calls.append(np.asarray(B).shape)
        return real(A, B, **kw)

    monkeypatch.setattr(gf_ops, "gf256_matmul", wrapper)
    return calls


def test_auto_backend_size_crossover(monkeypatch):
    calls = _counting(monkeypatch)
    code = RSCode(n=6, k=4, backend="auto")
    small = np.ones((4, 64), dtype=np.uint8)  # 256 B operand: LUT territory
    big_l = AUTO_KERNEL_MIN_BYTES // 4
    big = np.ones((4, big_l), dtype=np.uint8)  # exactly at the crossover
    code.encode(small)
    assert calls == [], "tiny operand must stay on the LUT path"
    code.encode(big)
    assert len(calls) == 1, "large operand must take the kernel path"
    np.testing.assert_array_equal(
        code.encode(big), RSCode(n=6, k=4).encode(big)
    )


def test_fused_group_decode_single_launch(monkeypatch):
    """decode_bytes_batch with SEVERAL distinct index-set groups and ragged
    lengths issues ONE kernel launch when group fusion is on (the TPU
    block-diagonal path; forced on here to pin correctness on CPU)."""
    vals = [_blob(i, 200 + 37 * i) for i in range(6)]
    subsets = [(1, 2, 3, 4), (0, 2, 3, 5), (1, 2, 3, 4), (0, 1, 2, 4),
               (2, 3, 4, 5), (0, 2, 3, 5)]
    enc = RSCode(n=6, k=4).encode_bytes_batch(vals)
    items = [
        ({i: frags[i] for i in sub}, orig)
        for (frags, orig), sub in zip(enc, subsets)
    ]
    want = RSCode(n=6, k=4, backend="numpy").decode_bytes_batch(items)
    assert want == vals
    calls = _counting(monkeypatch)
    fused = RSCode(n=6, k=4, backend="kernel", fuse_groups=True)
    assert fused.decode_bytes_batch(items) == vals
    assert len(calls) == 1, f"expected ONE fused launch, saw {len(calls)}"
    calls.clear()
    unfused = RSCode(n=6, k=4, backend="kernel", fuse_groups=False)
    assert unfused.decode_bytes_batch(items) == vals
    assert len(calls) == len(set(subsets)), "one launch per index-set group"


# ------------------------------------------------------------- e2e cycles
def _cycle(backend: str):
    """Full EC life cycle on one backend; returns every byte the store ever
    handed back plus the final server-side element map."""
    dss = DSS(DSSParams(algorithm="coaresecf", n_servers=6, parity_m=2,
                        seed=21, min_block=512, avg_block=1024, max_block=4096,
                        coding_backend=backend))
    w = dss.client("w")
    r = dss.client("r")
    outs = []
    blob = _blob(50, 20_000)
    dss.net.run_op(w.update("f", blob), client="w")
    outs.append(dss.net.run_op(r.read("f"), client="r"))
    blob2 = blob[:8000] + _blob(51, 1500) + blob[9000:]
    dss.net.run_op(w.update("f", blob2), client="w")
    outs.append(dss.net.run_op(r.read("f"), client="r"))
    # recon to a fresh server set (state transfer re-encodes on the backend)
    cfg = dss.make_config(fresh_servers=True)
    dss.net.run_op(w.recon("f", cfg), client="w")
    outs.append(dss.net.run_op(r.read("f"), client="r"))
    # crash + wipe + recover two servers, then repair
    down = list(cfg.servers[:1])
    dss.crash_servers(down)
    dss.wipe_servers(down)
    dss.recover_servers(down)
    dss.repair()
    outs.append(dss.net.run_op(r.read("f"), client="r"))
    elems = {
        (sid, key, t): e
        for sid, srv in sorted(dss.net.servers.items())
        for key, lst in sorted(srv.ec.items())
        for t, e in sorted(lst.items())
    }
    return [bytes(o) for o in outs], elems, blob, blob2


def test_e2e_kernel_bit_identical_to_numpy():
    """Acceptance (ISSUE 6): read/update/recon/repair under
    coding_backend="kernel" returns bytes identical to the numpy run — and
    leaves bit-identical coded elements on every server."""
    outs_np, elems_np, blob, blob2 = _cycle("numpy")
    outs_kr, elems_kr, _, _ = _cycle("kernel")
    assert outs_np[0] == blob and outs_np[1] == outs_np[2] == outs_np[3] == blob2
    assert outs_kr == outs_np
    assert elems_kr == elems_np


def test_checkpoint_coding_backend_plumbs():
    from repro.train.checkpoint import ECCheckpointStore

    store = ECCheckpointStore(n_hosts=5, parity=1, coding_backend="kernel")
    assert store.dss.net.coding_backend == "kernel"
    assert store.dss.params.coding_backend == "kernel"
    state = {"w": np.arange(4096, dtype=np.float32)}
    assert store.save(1, state).success
    step, got = store.restore()
    assert step == 1
    np.testing.assert_array_equal(got["w"], state["w"])


# ------------------------------------------------------- corruption / CRC
def _find_full_element(dss, obj="f", idx=0):
    for sid, srv in sorted(dss.net.servers.items()):
        lst = srv.ec.get((obj, idx), {})
        for t, e in lst.items():
            if e is not None and len(e) >= 3 and e[0]:
                return sid, t, e
    raise AssertionError("no checksummed element stored")


def test_put_elements_carry_crc():
    dss = DSS(DSSParams(algorithm="coaresec", n_servers=6, parity_m=4, seed=7))
    w = dss.client("w")
    dss.net.run_op(w.update("f", _blob(1, 3000)), client="w")
    _sid, _t, e = _find_full_element(dss)
    assert e[2] == zlib.crc32(e[0]) and element_crc_ok(e)


def test_read_drops_corrupt_fragment():
    """A bit-rotted stored element fails its CRC at collection: the read
    treats it as absent and still returns the written bytes."""
    dss = DSS(DSSParams(algorithm="coaresec", n_servers=6, parity_m=4, seed=8))
    w = dss.client("w")
    blob = _blob(2, 5000)
    dss.net.run_op(w.update("f", blob), client="w")
    sid, t, e = _find_full_element(dss)
    rotted = bytes([e[0][0] ^ 0xFF]) + e[0][1:]
    dss.net.servers[sid].ec[("f", 0)][t] = (rotted, e[1], e[2])
    assert not element_crc_ok(dss.net.servers[sid].ec[("f", 0)][t])
    r = dss.client("r")
    assert dss.net.run_op(r.read("f"), client="r") == blob


def test_repair_heals_corrupt_element():
    """The repair scan counts a corrupt holder as missing, and the server
    overwrites an element that fails its own stored checksum — and ONLY
    such an element (healthy elements keep their no-overwrite guarantee)."""
    dss = DSS(DSSParams(algorithm="coaresec", n_servers=6, parity_m=4, seed=9))
    w = dss.client("w")
    dss.net.run_op(w.update("f", _blob(3, 4000)), client="w")
    sid, t, e = _find_full_element(dss)
    rotted = bytes([e[0][0] ^ 0xFF]) + e[0][1:]
    dss.net.servers[sid].ec[("f", 0)][t] = (rotted, e[1], e[2])
    stats = dss.repair()
    assert stats[0]["missing"] >= 1 and stats[0]["applied"] >= 1
    healed = dss.net.servers[sid].ec[("f", 0)][t]
    assert element_crc_ok(healed) and healed[0] == e[0]
    # a second pass finds nothing to do
    stats2 = dss.repair()
    assert stats2[0]["missing"] == 0
    # direct push against a HEALTHY element is still refused
    srv = dss.net.servers[sid]
    kind, applied = srv.handle(
        "rc", ("ec-repair-push", "f", 0, t, (b"Z" * len(e[0]), e[1], 0), 8)
    )
    assert kind == "repair-ack" and not applied
    assert srv.ec[("f", 0)][t][0] == e[0]
