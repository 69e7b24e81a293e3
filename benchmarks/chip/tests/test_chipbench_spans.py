"""The program's spans as the benchmark reads them: the tracer's totals in a
traced tiny run, and the nesting of ``ares.*`` spans in a profiler trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import spans  # noqa: E402
from chipbench import trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CELLS = ["emulab_k6.ingest", "aws_k2.ingest", "emulab_k6.degraded_read"]
NEW = {"pad_s_per_GiB", "transfer_s_per_GiB", "padding_share_pct", "copy_s_per_GiB",
       "crc_s_per_GiB", "chunking_host_s_per_GiB", "framing_s_per_GiB", "server_s_per_GiB"}


@pytest.fixture(autouse=True)
def tracer_off():
    from repro import tracing

    tracing.enable(False)
    yield
    tracing.enable(False)


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_every_span_metric_its_cell_lists(cell, monkeypatch):
    from _tiny import run, tiny_cell

    line = run(cell, monkeypatch, traced=True)
    assert line["correct"], line["checks"]
    listed = {m["name"] for m in tiny_cell(cell).per_layer} & NEW
    assert listed == NEW - ({"chunking_host_s_per_GiB"} if "degraded" in cell else set())
    m = line["metrics"]
    assert listed <= set(m)
    assert all(m[n]["value"] > 0 for n in listed - {"padding_share_pct"})
    assert 0 <= m["padding_share_pct"]["value"] < 100
    assert "unattributed_idle_pct" not in m


def test_without_the_tracer_the_readers_report_nothing(monkeypatch):
    """The readers run on older commits too: there the import fails."""
    import repro

    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert spans.recording() is None
    assert spans.padding_pct() is None
    assert spans.self_s_per_gib(None, ("frame",)) is None


def _nest() -> list[tr.Event]:
    E = tr.Event
    return sorted([
        E("ares.step", 1.0, 5.0), E("ares.rs.encode", 2.0, 4.0),
        E("chipbench.device_call.gf256_matmul", 2.5, 3.5), E("ares.gf256.run", 3.0, 3.2),
        E("ares.handle", 6.0, 7.0), E("ares.frame", 8.0, 12.0),
    ], key=lambda e: (e.start, -e.end))


def test_each_instant_belongs_to_the_innermost_span():
    pieces = spans.owners(_nest(), 0.0, 10.0)
    own: dict = {}
    for a, b, name, _top in pieces:
        own[name] = own.get(name, 0.0) + b - a
    assert own == pytest.approx({
        "ares.step": 2.0, "ares.rs.encode": 1.0, "chipbench.device_call.gf256_matmul": 0.8,
        "ares.gf256.run": 0.2, "ares.handle": 1.0, "ares.frame": 2.0})  # frame cut at 10
    assert {top for *_, top in pieces} == {"ares.step", "ares.handle", "ares.frame"}
    # pieces are disjoint, in order, and make up the spans' union
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))
    assert sum(b - a for a, b, *_ in pieces) == pytest.approx(4.0 + 1.0 + 2.0)


def test_idle_device_time_goes_to_its_innermost_span():
    pieces = spans.owners(_nest(), 0.0, 10.0)
    idle = [(0.0, 3.1), (3.15, 6.5), (9.0, 10.0)]  # busy [3.1, 3.15] and [6.5, 9]
    got: dict = {}
    for (_a, _b, name, _t), s in zip(pieces, spans._overlap(pieces, idle)):
        got[name] = got.get(name, 0.0) + s
    assert got == pytest.approx({
        "ares.step": 2.0, "ares.rs.encode": 1.0, "chipbench.device_call.gf256_matmul": 0.8,
        "ares.gf256.run": 0.15, "ares.handle": 0.5, "ares.frame": 1.0})
    idle_s = sum(b - a for a, b in idle)
    # idle that no span covers: [0, 1] and [5, 6]
    assert idle_s - sum(got.values()) == pytest.approx(2.0)


def test_a_metadata_suffix_is_not_part_of_the_name():
    assert spans._base("ares.step#op=12#") == "ares.step"
    assert spans._base("ares.frame") == "ares.frame"


def test_a_trace_recorded_on_the_chip_gives_each_instant_one_owner(tmp_path):
    """``ingest_spans.xplane.pb.gz``: a 2 s traced window of
    ``emulab_k6.ingest`` (seed 4100000001) on one TPU v5 lite, through
    ``split.py``. The run printed window_s 2.009929449, busy_s
    0.023113564, 0.059838582 s of the window outside every span, all of it
    idle, and 1.922997065 s under ``ares.step`` and ``ares.handle`` over
    the whole trace, against the tracer's 1.917880939."""
    import gzip

    path = tmp_path / "ingest_spans.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "ingest_spans.xplane.pb.gz").read_bytes()))
    red = spans.reduce(str(path))
    assert red["window_s"] == pytest.approx(2.009929449)
    assert red["busy_s"] == pytest.approx(0.023113564)
    assert red["uncovered_s"] == pytest.approx(0.059838582)
    assert red["unattributed_idle_s"] == pytest.approx(0.059838582)
    assert red["protocol_all_s"] == pytest.approx(1.922997065)
    # every idle second has exactly one owner: a span, or none
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_s"].values()) + red["unattributed_idle_s"] == pytest.approx(idle)
    assert all(red["idle_s"][n] <= red["self_s"][n] + 1e-9 for n in red["idle_s"])
    # the self times make up the union of the spans inside the window
    lo, hi = tr.load(str(path)).window
    union = sum(b - a for a, b in tr.merged(tr._clip(spans.load_spans(str(path)), lo, hi)))
    assert sum(red["self_s"].values()) == pytest.approx(union)
    assert union == pytest.approx(red["window_s"] - red["uncovered_s"])
    names = set(red["self_s"])
    assert {"ares.step", "ares.handle", "ares.frame", "ares.cdc", "ares.gf256.get",
            "ares.cdc_kernel.run", "chipbench.device_call.gf256_matmul"} <= names
    assert not any("#" in n for n in names)


def test_the_split_row_joins_the_tracer_and_the_trace():
    import split

    rec = {"calls": {"step": 3, "gf256.get": 1}, "incl_s": {"step": 2.0, "gf256.get": 0.5},
           "self_s": {"step": 1.5, "gf256.get": 0.5}, "bytes": {"gf256.get": 64},
           "compiles": {}, "counts": {"gf256.launch": 1}, "protocol_s": 2.0}
    red = {"window_s": 4.0, "self_s": {"ares.step": 1.4, "ares.gf256.get": 0.5,
                                       "chipbench.payload": 1.0},
           "uncovered_s": 1.1, "protocol_all_s": 1.9, "busy_s": 0.2,
           "idle_s": {"ares.step": 1.3, "chipbench.payload": 1.0}, "unattributed_idle_s": 1.0}
    row = split.program_spans_row(rec, red)
    assert row["row"] == "program_spans"
    assert set(row["spans"]) == {"ares.step", "ares.gf256.get", "chipbench.payload"}
    assert row["spans"]["ares.step"] == {"calls": 3, "incl_s": 2.0, "self_s": 1.5, "bytes": 0,
                                         "compiles": 0, "trace_self_s": 1.4, "idle_s": 1.3}
    assert row["spans"]["chipbench.payload"] == {"trace_self_s": 1.0, "idle_s": 1.0}
    assert row["uncovered_pct"] == pytest.approx(27.5)
    assert row["unattributed_idle_pct"] == pytest.approx(25.0)
    assert row["protocol"] == {"trace_self_s": 1.9, "tracer_s": 2.0}
