"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on hand-made intervals, and on a small trace recorded on the chip."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _made() -> tr.Trace:
    ops = [tr.Event("copy", 0.5, 1.5), tr.Event("k", 1.0, 2.0, "gf256_matmul"),
           tr.Event("c", 4.0, 4.5, "cdc_gearhash"), tr.Event("late", 9.5, 11.0)]
    spans = [tr.Event("chipbench.device_call.gf256_matmul", 0.8, 2.2),
             tr.Event("chipbench.payload", 3.0, 3.5)]
    return tr.Trace(devices={"/device:TPU:0": ops}, spans=spans, window=(1.0, 10.0))


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _made()
    assert t.window_s == 9.0
    assert tr.busy_s(t) == pytest.approx(1.0 + 0.5 + 0.5)  # [1,2], [4,4.5], [9.5,10]


def test_kernel_time_sums_that_kernels_events():
    t = _made()
    assert tr.kernel_s(t, "gf256_matmul") == pytest.approx(1.0)
    assert tr.kernel_s(t, "cdc_gearhash") == pytest.approx(0.5)
    assert tr.kernel_s(t, "flash") is None


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = dict(tr.idle_gaps(_made()))
    assert gaps["device_call.gf256_matmul"] == pytest.approx(0.2)  # [2, 2.2]
    assert gaps["payload"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(9.0 - 2.0)
    assert tr.top_ops(_made())[0] == ["gf256_matmul", pytest.approx(1.0)]


def test_a_trace_recorded_on_the_chip_reduces_as_the_run_reported():
    """``ingest_small.xplane.pb``: a 0.3 s traced window of
    ``emulab_k6.ingest`` on one TPU v5 lite. The run itself printed
    busy_s 0.004096328, window_s 0.302907193, cdc_roofline 1.99926394,
    gf256_roofline 4.10264392 and device_idle_share 98.64766236, from
    needed bytes of 36569088 (CDC) and 49982350 (GF(256))."""
    t = tr.load(str(DATA / "ingest_small.xplane.pb"))
    assert list(t.devices) == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.302907193)
    assert tr.busy_s(t) == pytest.approx(0.004096328)
    assert 100 * 36569088 / 819e9 / tr.kernel_s(t, "cdc_gearhash") == pytest.approx(1.99926394)
    assert 100 * 49982350 / 819e9 / tr.kernel_s(t, "gf256_matmul") == pytest.approx(4.10264392)
    top = dict(tr.top_ops(t))
    assert top["cdc_gearhash"] > top["gf256_matmul"] > 0
    assert {"copy", "reshape.3"} <= set(top)
    gaps = dict(tr.idle_gaps(t))
    assert {"protocol_or_driver", "device_call.gf256_matmul", "device_call.cdc_gearhash",
            "payload"} <= set(gaps)
    assert sum(gaps.values()) == pytest.approx(t.window_s - tr.busy_s(t))
