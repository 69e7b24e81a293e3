"""Column windows of the batched coding calls.

``RSCode.encode_bytes_batch`` and ``decode_bytes_batch`` lay their values
side by side as one operand, but pack, multiply and unpack it one window of
``dispatch.GF_SLICE_COLS`` columns at a time. Here the window is cut to a few
hundred columns, so values straddle window edges, span three or more
windows and end in a ragged last one, and every result is held to a plain
per-value reference: ``bytes_to_rows`` and the numpy LUT product.
"""
import zlib

import numpy as np
import pytest

from repro import tracing
from repro.erasure.gf import gf_matmul_np
from repro.erasure.rs import RSCode, _decoder_cached, bytes_to_rows, rows_to_bytes
from repro.kernels import dispatch
from repro.kernels.gf256_matmul import ops as gf_ops

WINDOW = 256  # dispatch.GF_SLICE_COLS patched down: 2^21 on the chip

# (k, m, value lengths): the widths are ceil(len / k) columns each (1 for an
# empty value), laid side by side
CASES = {
    "one_window": (6, 5, [600, 6 * 40 + 1, 0]),           # 100 + 41 + 1 columns
    "straddles_an_edge": (6, 5, [6 * 200, 6 * 100 + 5]),  # 200 + 101: edge at 256
    "spans_three": (6, 5, [7, 6 * 3 * WINDOW + 1, 30]),   # 2 + 769 + 5 columns
    "ragged_last": (4, 2, [4 * WINDOW, 4 * WINDOW, 9]),   # 256 + 256 + 3
    "empty_values": (6, 5, [0, 6 * 300, 0, 0]),           # 1 + 300 + 1 + 1
    "aws_shape": (2, 4, [2 * 333, 1, 2 * 200 + 1]),       # 333 + 1 + 201
    "no_parity": (4, 0, [4 * 300 + 3, 0, 11]),
}


@pytest.fixture
def windows(monkeypatch):
    monkeypatch.setattr(dispatch, "GF_SLICE_COLS", WINDOW)


def _values(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in lengths]


def _width(lengths, k):
    return sum(-(-n // k) or 1 for n in lengths)


def _encoded(code, value):
    """The plain reference: the value's padded (k, L) rows and the LUT
    product of the parity matrix with them."""
    rows, orig = bytes_to_rows(value, code.k)
    frags = [rows[i].tobytes() for i in range(code.k)]
    if code.m:
        parity = gf_matmul_np(code.parity_matrix, rows)
        frags += [parity[j].tobytes() for j in range(code.m)]
    return frags, orig, [zlib.crc32(f) for f in frags]


def _decoded(code, fragments, orig):
    idxs = code._choose_idxs(fragments)
    rows = np.stack([np.frombuffer(fragments[i], dtype=np.uint8) for i in idxs])
    data = gf_matmul_np(_decoder_cached(code.n, code.k, idxs), rows)
    return rows_to_bytes(data, orig)


def _spy(monkeypatch, operands=None):
    calls = []
    real = gf_ops.gf256_matmul

    def spy(A, B, **kw):
        calls.append(np.shape(B))
        if operands is not None:
            operands.append(np.array(B))
        return real(A, B, **kw)

    monkeypatch.setattr(gf_ops, "gf256_matmul", spy)
    return calls


def _whole_operand(rows_of, items, idxs_of):
    """The operand the windows cut, built whole: each item's (k, L) rows
    side by side, and for several index sets each set's operand stacked
    under the last, zero past its own width."""
    sets = {}
    for item in items:
        sets.setdefault(idxs_of(item), []).append(rows_of(item))
    blocks = [np.concatenate(rows, axis=1) for rows in sets.values()]
    width = max(b.shape[1] for b in blocks)
    return np.concatenate([np.pad(b, ((0, 0), (0, width - b.shape[1]))) for b in blocks])


def _traced(fn):
    tracing.enable(True)
    try:
        out = fn()
        return out, tracing.recording()["counts"]
    finally:
        tracing.enable(False)


@pytest.mark.parametrize("backend", ["kernel", "numpy"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_windowed_encode_is_the_per_value_reference(case, backend, windows):
    k, m, lengths = CASES[case]
    code = RSCode(n=k + m, k=k, backend=backend)
    values = _values(lengths, seed=len(case))
    got = code.encode_bytes_batch(values, with_crc=True)
    assert got == [_encoded(code, v) for v in values]
    assert got == [code.encode_bytes(v, with_crc=True) for v in values]
    assert code.encode_bytes_batch(values) == [(f, o) for f, o, _ in got]


# which fragments each item keeps: one index set, two, or all systematic
SURVIVORS = {
    "one_set": lambda j: {0, 10},
    "two_sets": lambda j: {0, 10} if j % 2 else {1, 2},
    "mixed_with_systematic": lambda j: {0, 10} if j % 3 else {9, 10},
}


@pytest.mark.parametrize("fuse", [False, True], ids=["per_group", "block_diagonal"])
@pytest.mark.parametrize("lost", sorted(SURVIVORS))
def test_windowed_decode_is_the_per_value_reference(lost, fuse, windows):
    code = RSCode(n=11, k=6, backend="kernel", fuse_groups=fuse)
    values = _values([6 * 3 * WINDOW + 1, 0, 6 * 200, 6 * 100 + 5, 13, 6 * WINDOW], seed=3)
    items = []
    for j, (frags, orig, crcs) in enumerate(code.encode_bytes_batch(values, with_crc=True)):
        keep = {i: f for i, f in enumerate(frags) if i not in SURVIVORS[lost](j)}
        items.append((keep, orig, {i: crcs[i] for i in keep}))
    got = code.decode_bytes_batch(items)
    assert got == values
    assert got == [_decoded(code, frags, orig) for frags, orig, _ in items]
    assert got == [code.decode_bytes(*item) for item in items]


@pytest.mark.parametrize("call", ["encode", "decode", "fused_decode"])
def test_one_launch_per_window_and_none_wider(call, windows, monkeypatch):
    """Each window is one ``gf256_matmul`` call no wider than a window, and
    each is counted as one slice of the larger call: the launch counter
    keeps equal to the calls the benchmark's meter sees."""
    lengths = [6 * 3 * WINDOW + 1, 6 * 200, 0, 6 * 100 + 5]
    code = RSCode(n=11, k=6, backend="kernel", fuse_groups=call == "fused_decode")
    values = _values(lengths, seed=5)
    encoded = code.encode_bytes_batch(values)
    if call == "encode":
        rows, width = 6, _width(lengths, 6)
        run = lambda: code.encode_bytes_batch(values)  # noqa: E731
    else:  # one index set, or two fused over the wider set's columns
        two = call == "fused_decode"
        items = [({i: f for i, f in enumerate(frags) if i > (j % 2 if two else 0)}, orig)
                 for j, (frags, orig) in enumerate(encoded)]
        widths = [-(-n // 6) or 1 for n in lengths]
        rows, width = (12, max(sum(widths[0::2]), sum(widths[1::2]))) if two \
            else (6, sum(widths))
        run = lambda: code.decode_bytes_batch(items)  # noqa: E731
    operands = []
    calls = _spy(monkeypatch, operands)
    out, counts = _traced(run)
    assert out == (encoded if call == "encode" else values)
    n = -(-width // WINDOW)
    assert n >= 3
    assert calls == [(rows, WINDOW)] * (n - 1) + [(rows, width - (n - 1) * WINDOW)]
    if call == "encode":
        whole = _whole_operand(lambda v: bytes_to_rows(v, 6)[0], values, lambda v: ())
    else:
        whole = _whole_operand(
            lambda it: np.stack([np.frombuffer(it[0][i], np.uint8)
                                 for i in code._choose_idxs(it[0])]),
            items, lambda it: code._choose_idxs(it[0]))
    np.testing.assert_array_equal(np.concatenate(operands, axis=1), whole)
    assert counts["gf256.launch"] == len(calls)
    assert counts[f"gf256.launch.rows.{rows}"] == len(calls)
    assert counts["gf256.slices"] == n
    assert counts["gf256.bytes_in_sliced"] == counts["gf256.bytes_in"] == rows * width


@pytest.mark.parametrize("call", ["encode", "decode"])
def test_a_call_within_one_window_launches_once(call, windows, monkeypatch):
    """A call no wider than one window is one launch of its whole operand,
    as before the windows, and counts no slice."""
    lengths = [6 * 100, 6 * 150 + 2, 0]  # 100 + 151 + 1 = 252 columns
    code = RSCode(n=11, k=6, backend="kernel")
    values = _values(lengths, seed=7)
    encoded = code.encode_bytes_batch(values)
    items = [({i: f for i, f in enumerate(frags) if i != 2}, orig) for frags, orig in encoded]
    run = (lambda: code.encode_bytes_batch(values)) if call == "encode" \
        else (lambda: code.decode_bytes_batch(items))
    calls = _spy(monkeypatch)
    out, counts = _traced(run)
    assert out == (encoded if call == "encode" else values)
    assert calls == [(6, _width(lengths, 6))]
    assert counts["gf256.launch"] == 1
    assert "gf256.slices" not in counts


def test_the_numpy_backend_never_calls_the_kernel(windows, monkeypatch):
    calls = _spy(monkeypatch)
    code = RSCode(n=11, k=6, backend="numpy")
    values = _values([6 * 3 * WINDOW + 1, 5], seed=9)
    encoded = code.encode_bytes_batch(values, with_crc=True)
    items = [({i: f for i, f in enumerate(frags) if i}, orig, dict(enumerate(crcs)))
             for frags, orig, crcs in encoded]
    assert code.decode_bytes_batch(items) == values
    assert calls == []
