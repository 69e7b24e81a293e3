"""Each cell's set-up, window and check at a tiny size on the CPU, through
``run_cell``: the whole run behind the entry's look for a chip."""
import pytest

from _tiny import run

CELLS = ["emulab_k6.ingest", "aws_k2.ingest", "emulab_k6.degraded_read", "emulab_k6.edit"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_reports_its_metrics(cell, monkeypatch):
    from _tiny import tiny_cell

    # a window long enough that ops finish in it on a loaded CPU: an edit
    # of the tiny cell's largest file takes some tenths of a second there
    line = run(cell, monkeypatch, seed=3_000_000_019, seconds=2.0)
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


def test_edits_flip_one_byte_of_an_owned_file_per_version():
    from chipbench.traffic import Traffic
    from _tiny import tiny_cell

    cell = tiny_cell("emulab_k6.edit")
    t = Traffic(cell.traffic, cell.config, 2**33 + 3)
    writers = cell.config["writers"]
    # every file preloaded once, by the session of the slot that edits it
    pre = t.preload()
    assert len({o.fid for o in pre}) == len(pre) == writers * len(t.sizes)
    assert all(o.session == f"writer{o.index[1]}" for o in pre)
    for slot in (0, writers - 1):
        stream = t.stream("edit", slot)
        ops = [next(stream) for _ in range(2 * len(t.sizes))]
        assert {o.session for o in ops} == {f"writer{slot}"}
        for o in ops:
            _edit, owner, s, v = o.index
            assert owner == slot and o.fid == t.file(slot, s) and o.size == t.sizes[s]
            new, old = t.payload(o), t.version(slot, s, v - 1)
            assert new == t.version(slot, s, v)  # a save and a rebuild agree
            diff = [i for i, (a, b) in enumerate(zip(new, old)) if a != b]
            assert diff == [t.flip_at(slot, s, v)] and new[diff[0]] == old[diff[0]] ^ 0xFF
        # the window carries on from the warm-up's versions, one per round
        firsts = {o.fid: o.index[3] for o in reversed(ops)}
        assert set(firsts.values()) == {t.first_version(slot) + 1}
    # readers read the edited files, each from a session of its own
    stream = t.stream("read", 2)
    reads = [next(stream) for _ in range(30)]
    assert {o.fid for o in reads} <= {o.fid for o in pre}
    assert len({o.session for o in reads}) == len(reads)


def _edit_window(writes: list, read):
    from chipbench.loop import Done, Window
    from chipbench.traffic import EDIT

    ops = [Done("write", "e1.0", 0, issued=i, done=d, index=(EDIT, 1, 0, v))
           for v, (i, d) in enumerate(writes, start=1)]
    return Window(start=0.0, close=100.0, ops=ops + [read])


# version 1 written over [1, 2], 2 over [3, 4], 3 over [6, 7]; the read is
# issued at 2.5 and completes at 5: versions 1 (acknowledged before the
# read was issued) and 2 (written during the read) may come back
@pytest.mark.parametrize("answer,wrong", [(0, True), (1, False), (2, False), (3, True)])
def test_a_read_of_an_edited_file_may_return_any_version_it_overlapped(answer, wrong):
    from chipbench import check
    from chipbench.loop import Done
    from chipbench.traffic import EDIT, Traffic
    from _tiny import tiny_cell

    cell = tiny_cell("emulab_k6.edit")
    t = Traffic(cell.traffic, cell.config, 11)
    read = Done("read", "e1.0", t.sizes[0], issued=2.5, done=5.0, index=(EDIT, 1, 0),
                answer=t.version(1, 0, answer))
    versions = check.Versions(t, _edit_window([(1, 2), (3, 4), (6, 7)], read))
    assert list(versions.admissible(read)) == [1, 2]
    assert versions.newest("e1.0") == 3 and versions.newest("e2.0") == 0
    assert check._wrong_read(t, versions, read) is wrong


# what each mix's generator issued before the edit mix was added, on a
# seed that the older generator did not turn (it started slot s at size
# s + seed mod 5; it now starts it at size s for every seed): the first ops
# of set-up, warm-up and two slots, with a digest of their content
PINNED = {
    "emulab_k6.ingest": [
        ("write", "warm0", 1048576, "writer0", (1, 1048576, 0), False, "f744c5f5ecbace05"),
        ("write", "warm1", 2097152, "writer0", (1, 1048576, 1), False, "53962d53a37a80e6"),
        ("write", "w0.0", 1048576, "writer0", (1, 0, 0), False, "bed6ffb12d07bcf4"),
        ("write", "w0.1", 2097152, "writer0", (1, 0, 1), False, "50ecb354b9f6a03b"),
        ("write", "w0.2", 4194304, "writer0", (1, 0, 2), False, "10e5e71c72ecac39"),
        ("write", "w4.0", 16777216, "writer4", (1, 4, 0), False, "b0febd93e15e70c3"),
        ("write", "w4.1", 1048576, "writer4", (1, 4, 1), False, "4cec2b044b68f758"),
        ("write", "w4.2", 2097152, "writer4", (1, 4, 2), False, "06c6481db7742bee"),
    ],
    "emulab_k6.degraded_read": [
        ("write", "p0.0", 1048576, "", (3, 0, 0), False, "b68f0d5b23a6ce0a"),
        ("write", "p1.0", 2097152, "", (3, 1, 0), False, "51a32d03e8c62e54"),
        ("write", "p2.0", 4194304, "", (3, 2, 0), False, "8e92d5e61409846f"),
        ("read", "p0.0", 1048576, "warm-reader0", (3, 0, 0), False, "b68f0d5b23a6ce0a"),
        ("read", "p1.0", 2097152, "warm-reader1", (3, 1, 0), False, "51a32d03e8c62e54"),
        ("read", "p0.0", 1048576, "reader0.0", (3, 0, 0), False, "b68f0d5b23a6ce0a"),
        ("read", "p1.2", 2097152, "reader0.1", (3, 1, 2), False, "cb272c6cdbc5752a"),
        ("read", "p2.4", 4194304, "reader0.2", (3, 2, 4), False, "0bb63c658c331be4"),
        ("read", "p4.0", 16777216, "reader4.0", (3, 4, 0), False, "bae5592b2bb5847b"),
        ("read", "p0.3", 1048576, "reader4.1", (3, 0, 3), False, "6fbb935cfddb0d50"),
        ("read", "p1.1", 2097152, "reader4.2", (3, 1, 1), False, "78330967ef9149c8"),
    ],
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_the_older_mixes_issue_what_they_always_issued(cell):
    import hashlib

    from chipbench import spec
    from chipbench.traffic import Traffic

    c = spec.load_cell(cell)
    t = Traffic(c.traffic, c.config, 2**33 + 3)
    kind = "write" if t.slots("write") else "read"
    streams = [t.stream(kind, slot) for slot in (0, 4)]
    ops = t.preload()[:3] + t.warmup()[:2] + [next(s) for s in streams for _ in range(3)]
    got = [(o.kind, o.fid, o.size, o.session, o.index, o.keep,
            hashlib.sha1(t.payload(o)).hexdigest()[:16]) for o in ops]
    assert got == PINNED[cell]


@pytest.mark.parametrize("cell", ["aws_k2.ingest"])
def test_every_seed_replays_one_schedule(cell, monkeypatch):
    """Runs on seeds that write other content complete the same ops in the
    same order at the same virtual times (to a block header's wire time),
    so that which ops overlap, and the latency tail with it, is not the
    seed's. On the WAN's delays no such difference parts two ops' order."""
    from chipbench import harness, loop
    from chipbench.traffic import Traffic
    from _tiny import tiny_cell

    build, finish = harness.build_store, loop.ClosedLoop._finish
    stores, done = [], []

    def stamp(spec, fut, rec, now):
        done[-1].append((spec.session, spec.fid, spec.size, stores[-1].net.now))
        return finish(spec, fut, rec, now)

    monkeypatch.setattr(harness, "build_store", lambda *args: stores.append(build(*args))
                        or stores[-1])
    monkeypatch.setattr(loop.ClosedLoop, "_finish", staticmethod(stamp))
    seeds = (2**33 + 11, 7)
    c = tiny_cell(cell)
    first = next(Traffic(c.traffic, c.config, seeds[0]).stream("write", 0))
    assert len({Traffic(c.traffic, c.config, s).payload(first) for s in seeds}) == 2
    for seed in seeds:
        done.append([])
        assert run(cell, monkeypatch, seed=seed, seconds=0.5)["correct"]
    # the shorter run's last op of each slot is drained after its close,
    # when no new op competes, so only the ops before those compare
    a, b = done
    n = min(len(a), len(b)) - c.config["writers"]
    assert n > 20 and [x[:3] for x in a[:n]] == [x[:3] for x in b[:n]]
    assert max(abs(x[3] - y[3]) for x, y in zip(a[:n], b[:n])) < 1e-3
