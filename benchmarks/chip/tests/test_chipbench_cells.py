"""Each cell's set-up, window and check at a tiny size on the CPU, through
``run_cell``: the whole run behind the entry's look for a chip."""
import pytest

from _tiny import run

CELLS = ["emulab_k6.ingest", "aws_k2.ingest", "emulab_k6.degraded_read"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_reports_its_metrics(cell, monkeypatch):
    from _tiny import tiny_cell

    line = run(cell, monkeypatch, seed=3_000_000_019)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in tiny_cell(cell).end_to_end}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_a_traced_run_reports_host_layers_and_no_device_numbers(monkeypatch):
    line = run("emulab_k6.ingest", monkeypatch, traced=True)
    assert line["correct"]
    m = line["metrics"]
    # the CPU has no device plane: rooflines and idle share stay silent
    assert {"driver_s_per_GiB", "protocol_s_per_GiB", "device_call_s_per_GiB"} <= set(m)
    assert not {"cdc_roofline", "gf256_roofline", "device_idle_share"} & set(m)
    assert line["breakdown"]["device_ops"] == []


def test_the_same_seed_makes_the_same_content():
    from chipbench.traffic import Traffic
    from _tiny import tiny_cell

    cell = tiny_cell("emulab_k6.ingest")
    a = Traffic(cell.traffic, cell.config, 2**33 + 1)
    b = Traffic(cell.traffic, cell.config, 2**33 + 1)
    sa, sb = a.stream("write", 3), b.stream("write", 3)
    ops = [next(sa) for _ in range(10)]
    assert ops == [next(sb) for _ in range(10)]
    assert [a.payload(o) for o in ops] == [b.payload(o) for o in ops]
    # each round of five ops holds every size once
    assert sorted(o.size for o in ops[:5]) == sorted(a.sizes)
    assert len({a.payload(o) for o in ops}) == 10


def test_kept_reads_are_drawn_from_the_seed_over_the_whole_stream():
    from chipbench.traffic import Traffic
    from _tiny import tiny_cell

    cell = tiny_cell("emulab_k6.degraded_read")
    share = cell.traffic["check"]["reads_kept_share"]
    streams = [Traffic(cell.traffic, cell.config, seed).stream("read", 2)
               for seed in (5, 5, 6)]
    ops = [[next(s) for _ in range(4000)] for s in streams]
    kept = [[j for j, o in enumerate(run) if o.keep] for run in ops]
    assert kept[0] == kept[1] != kept[2]
    # as many kept in the last quarter of the stream as in the first
    for k in kept:
        first, last = sum(j < 1000 for j in k), sum(j >= 3000 for j in k)
        assert abs(first - last) < 0.5 * 1000 * share
    # every read comes from a client of its own
    assert len({o.session for o in ops[0]}) == len(ops[0])


def test_client_counts_come_from_the_configuration():
    from chipbench.traffic import Traffic
    from _tiny import tiny_cell

    for name, kind, other in (("emulab_k6.ingest", "write", "read"),
                              ("emulab_k6.degraded_read", "read", "write")):
        cell = tiny_cell(name)
        cell.config.update(writers=3, readers=4)
        t = Traffic(cell.traffic, cell.config, 1)
        assert t.slots(kind) == cell.config[{"write": "writers", "read": "readers"}[kind]]
        assert t.slots(other) == 0
