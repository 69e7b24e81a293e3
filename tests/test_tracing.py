"""The store's tracer (``repro.tracing``): off it is a shared no-op that
changes nothing; on it nests spans, counts launches as the benchmark's
meter does, makes up ``Network.protocol_time`` and puts compiles under the
span that ran them."""
import time
from pathlib import Path

import numpy as np
import pytest

from repro import tracing
from repro.core import DSS, DSSParams
from repro.erasure import rs

KiB = 1 << 10
BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


@pytest.fixture(autouse=True)
def tracer_off():
    tracing.enable(False)
    yield
    tracing.enable(False)


def _store(fast: bool) -> DSS:
    """The paper's Emulab shape (11 servers, k = 6) at KiB blocks, every
    product on the kernel path."""
    return DSS(DSSParams(
        algorithm="coaresecf", n_servers=11, parity_m=5, seed=4, indexed=True,
        min_block=2 * KiB, avg_block=2 * KiB, max_block=8 * KiB,
        coding_backend="kernel", fast_net=fast,
    ))


def _write_then_degraded_read(dss: DSS) -> list[bytes]:
    rng = np.random.default_rng(9)
    data = {f"f{i}": rng.bytes(n) for i, n in enumerate((40 * KiB, 9 * KiB, 3 * KiB))}
    writer = dss.session("w")
    assert all(f.result()["success"] for f in [writer.write(k, v) for k, v in data.items()])
    dss.crash_servers([dss.c0.servers[0], dss.c0.servers[10]])
    got = [f.result() for f in [dss.session(f"r{k}").read(k) for k in data]]
    assert got == list(data.values())
    return got


def _fingerprint(dss: DSS, answers: list[bytes]) -> tuple:
    net = dss.net
    stored = sorted((sid, repr(sorted(srv.ec.items()))) for sid, srv in net.servers.items())
    events = [(f.op_id, f.kind, f.client, f.start, f.end) for f in net.futures]
    return (answers, stored, events, net.now, net.events_processed, net.rpc_rounds,
            net.msg_count, net.bytes_sent, net.client_counters)


def test_off_is_one_shared_no_op_that_records_nothing():
    before = tracing.snapshot()
    assert tracing.span("step") is tracing.span("gf256.run", nbytes=8, op=3) is tracing.OFF
    with tracing.span("rs.encode"):
        tracing.count("gf256.launch")
    _write_then_degraded_read(_store(True))
    assert tracing.snapshot() == before


@pytest.mark.parametrize("fast", [True, False], ids=["fast", "legacy"])
def test_on_and_off_store_read_and_schedule_the_same_bytes(fast):
    off = _store(fast)
    off_fp = _fingerprint(off, _write_then_degraded_read(off))
    tracing.enable(True)
    on = _store(fast)
    on_fp = _fingerprint(on, _write_then_degraded_read(on))
    rec = tracing.recording()
    assert rec["calls"]["step"] > 0 and rec["calls"]["handle"] > 0
    assert rec["calls"]["rs.decode"] > 0 and rec["counts"]["gf256.launch"] > 0
    assert on_fp == off_fp


def test_self_time_is_inclusive_less_children():
    tracing.enable(True)
    with tracing.span("outer"):
        time.sleep(0.002)
        with tracing.span("inner", nbytes=5):
            time.sleep(0.003)
            with tracing.span("leaf"):
                time.sleep(0.001)
        with tracing.span("inner", nbytes=7):
            time.sleep(0.001)
    rec = tracing.recording()
    incl, self_s = rec["incl_s"], rec["self_s"]
    assert rec["calls"] == {"outer": 1, "inner": 2, "leaf": 1}
    assert rec["bytes"] == {"inner": 12}
    assert self_s["leaf"] == incl["leaf"]
    assert self_s["inner"] == pytest.approx(incl["inner"] - incl["leaf"], abs=1e-9)
    assert self_s["outer"] == pytest.approx(incl["outer"] - incl["inner"], abs=1e-9)
    assert self_s["outer"] >= 0.002 and incl["outer"] >= 0.007
    # the three self times make up the outer span
    assert sum(self_s.values()) == pytest.approx(incl["outer"], abs=1e-9)


def test_the_launch_counter_agrees_with_the_benchmark_meter(monkeypatch):
    monkeypatch.syspath_prepend(str(BENCH))
    from chipbench.meter import GF, Meter

    monkeypatch.setattr(rs, "AUTO_KERNEL_MIN_BYTES", 1 * KiB)
    tracing.enable(True)
    dss = _store(True)
    with Meter(code_k=dss.c0.k) as meter:
        _write_then_degraded_read(dss)
    counts = tracing.recording()["counts"]
    assert meter.calls[GF] > 0
    assert counts["gf256.launch"] == meter.calls[GF]
    assert counts["gf256.launch.rows.6"] == meter.calls[GF]
    assert counts["gf256.launch.flat"] == counts["gf256.launch"]
    assert counts["gf256.bytes_in_padded"] >= counts["gf256.bytes_in"] > 0


def test_protocol_time_is_the_outermost_step_and_handle_spans():
    dss = _store(True)
    dss.net.profile_protocol = True
    assert tracing.enabled()
    dss.net.protocol_time = 0.0
    _write_then_degraded_read(dss)
    dss.net.profile_protocol = False
    rec = tracing.recording()
    assert dss.net.protocol_time > 0
    assert dss.net.protocol_time == pytest.approx(
        rec["incl_s"]["step"] + rec["incl_s"]["handle"], rel=1e-9)
    # off, it stands still; a reset starts it again from the value given
    done = dss.net.protocol_time
    _write_then_degraded_read(_store(True))
    assert dss.net.protocol_time == done
    dss.net.protocol_time = 0.0
    assert dss.net.protocol_time == 0.0


def test_a_new_width_compiles_under_the_run_span():
    from repro.kernels.gf256_matmul.ops import gf256_matmul

    tracing.enable(True)
    A = np.arange(1, 8, dtype=np.uint8).reshape(7, 1)
    out = gf256_matmul(A, np.ones((1, 3 * 2**13 + 1), np.uint8))  # (7, 1) at a 32 Ki bucket
    assert out.shape == (7, 3 * 2**13 + 1)
    rec = tracing.recording()
    assert rec["compiles"].get("gf256.run", 0) >= 1
    assert not set(rec["compiles"]) - {"gf256.run"}
    # one flat launch: k * L bytes sent, k * Lp with padding, m * Lp back
    assert rec["counts"]["gf256.launch.rows.1"] == rec["counts"]["gf256.launch.flat"] == 1
    assert rec["counts"]["gf256.bytes_in"] == 3 * 2**13 + 1
    assert rec["counts"]["gf256.bytes_in_padded"] == 2**15
    assert rec["counts"]["gf256.bytes_out"] == 7 * 2**15
    # the same width again compiles nothing
    gf256_matmul(A, np.ones((1, 2**15), np.uint8))
    assert tracing.recording()["compiles"] == rec["compiles"]

