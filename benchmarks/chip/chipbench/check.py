"""The comparison that decides ``correct``, made once the window has closed.

Each number compared is a count with the limit 0: an exact comparison.

``failed_ops``
    ops that raised, were not applied, or never completed within a minute
    past the close, in set-up or in the window.
``stored_blocks_wrong``
    for a sample of files drawn from the seed, the longest among them: data
    blocks whose stored coded fragments, on every server, differ from the
    plain reference's: the reference chunks the file's content (for an
    edited file, its newest applied version), frames each chunk as a block
    value and RS-encodes it. An index entry whose newest value is the bare
    block header is a tombstone, a block the diff emptied: it is skipped,
    and the others are compared in order. A wrong chunk boundary, a wrong
    parity byte, a missing fragment, or a block list of another length
    each counts. An unreadable index counts as one.
``readback_wrong``
    the same sampled files read back through the store with the holders of
    ``readback_down_fragments`` crashed, so the decode has to use the parity
    the chip produced: reads whose bytes differ from the content written.
``reads_wrong``
    the window's kept read answers (a share drawn from the seed, spread over
    the whole window) whose bytes differ from the content of the object
    read; a window with none kept counts one. A read of an edited file may
    return any version from the newest whose write was acknowledged before
    the read was issued to the newest whose write was issued before the
    read completed, by the loop's clock.
``history_violations``
    1 when the store's own recorded history fails its Wing-Gong
    linearizability check.
"""
from __future__ import annotations

from chipbench import reference
from chipbench.traffic import EDIT, OpSpec

# a block value with no pointer and no data: a block the diff emptied
TOMBSTONE = b"\x00\x00"
LIMITS = {"failed_ops": 0, "stored_blocks_wrong": 0, "readback_wrong": 0,
          "reads_wrong": 0, "history_violations": 0}


def _latest(dss, sid: str, obj: str):
    """The newest coded element ``(fragment, orig_len, ...)`` a server
    holds for ``obj``, or None."""
    lst = dss.net.servers[sid].ec.get((obj, 0)) or {}
    held = [(t, e) for t, e in lst.items()
            if isinstance(e, tuple) and isinstance(e[0], bytes) and e[0]]
    return max(held, key=lambda te: te[0])[1] if held else None


def _stored_value(dss, obj: str) -> bytes | None:
    """A value as its systematic fragments store it."""
    cfg = dss.c0
    elems = [_latest(dss, sid, obj) for sid in cfg.servers[: cfg.k]]
    if any(e is None for e in elems):
        return None
    return b"".join(e[0] for e in elems)[: elems[0][1]]


def stored_blocks_wrong(dss, config: dict, files: dict[str, bytes]) -> int:
    """Blocks of ``files`` (fid -> content) stored otherwise than the
    reference would store them."""
    cfg = dss.c0
    wrong = 0
    for fid, content in files.items():
        want = reference.block_values(content, config["min_block"],
                                      config["avg_block"], config["max_block"])
        raw = _stored_value(dss, reference.genesis_id(fid))
        try:
            index = reference.parse_genesis(raw) if raw else None
        except ValueError:
            index = None
        if index is None:
            wrong += 1
            continue
        live = [bid for bid in index if _stored_value(dss, bid) != TOMBSTONE]
        wrong += abs(len(live) - len(want))
        for bid, value in zip(live, want):
            ref = reference.fragments(value, cfg.n, cfg.k)
            got = [_latest(dss, sid, bid) for sid in cfg.servers]
            wrong += any(e is None or e[0] != ref[i] or e[1] != len(value)
                         for i, e in enumerate(got))
    return wrong


def readback_wrong(dss, files: dict[str, bytes], down: list[int]) -> int:
    servers = [dss.c0.servers[i] for i in down]
    dss.crash_servers(servers)
    try:
        wrong = 0
        for j, (fid, content) in enumerate(files.items()):
            fut = dss.session(f"check-reader{j}").read(fid)
            try:
                wrong += fut.result() != content
            except Exception:  # noqa: BLE001 - a read that fails is a wrong read
                wrong += 1
        return wrong
    finally:
        dss.recover_servers(servers, wipe=False)


def history_violations(dss) -> int:
    from repro.analysis.linearize import LinearizabilityError

    try:
        dss.check_history()
    except LinearizabilityError:
        return 1
    return 0


class Versions:
    """Which versions of each edited file the window's writes made, and
    when (the loop's clock): each file's newest applied version, and the
    versions a read may return."""

    def __init__(self, traffic, win) -> None:
        self.first: dict[str, int] = {}
        self.writes: dict[str, list] = {}
        for d in win.ops:
            if d.kind == "write" and d.index[0] == EDIT:
                self.writes.setdefault(d.fid, []).append(d)
        for slot in range(traffic.slots("edit")):
            for s in range(len(traffic.sizes)):
                self.first[traffic.file(slot, s)] = traffic.first_version(slot)

    def newest(self, fid: str) -> int:
        return max([self.first[fid]] + [d.index[3] for d in self.writes.get(fid, [])
                                        if d.error is None])

    def admissible(self, read) -> range:
        writes = self.writes.get(read.fid, [])
        lo = max([self.first[read.fid]] + [d.index[3] for d in writes
                                           if d.error is None and d.done <= read.issued])
        hi = max([self.first[read.fid]] + [d.index[3] for d in writes
                                           if d.issued <= read.done])
        return range(lo, hi + 1)


def _wrong_read(traffic, versions: Versions, d) -> bool:
    _edit, slot, s = d.index
    return not any(d.answer == traffic.version(slot, s, v)
                   for v in versions.admissible(d))


def run_checks(dss, config: dict, traffic, win) -> tuple[dict, dict]:
    """``(numbers, notes)``: each compared number, and what was sampled."""
    dss.net.run()  # deliver what is still in flight to the other servers
    numbers = {"failed_ops": sum(d.error is not None for d in win.ops) + len(win.lost)}
    notes: dict = {}
    check = traffic.params.get("check", {})

    def content(d) -> bytes:
        return traffic.payload(OpSpec(d.kind, d.fid, d.size, "", d.index))

    versions = Versions(traffic, win) if traffic.edits else None
    if traffic.slots("write") or traffic.slots("edit"):
        done = [d for d in win.ops if d.kind == "write" and d.error is None]
        if versions is None:
            sample = traffic.sample(done, int(check.get("writes", 0)), lambda d: d.size)
            files = {d.fid: content(d) for d in sample}
        else:  # each edited file once, at its newest applied version
            edited = list({d.fid: d for d in done}.values())
            sample = traffic.sample(edited, int(check.get("files", 0)), lambda d: d.size)
            files = {d.fid: traffic.version(*d.index[1:3], versions.newest(d.fid))
                     for d in sample}
        numbers["stored_blocks_wrong"] = stored_blocks_wrong(dss, config, files) + (not files)
        numbers["readback_wrong"] = readback_wrong(
            dss, files, traffic.fragments("readback_down_fragments")) + (not files)
        notes.update(files_checked=len(files), bytes_checked=sum(map(len, files.values())))
    if traffic.slots("read"):
        kept = [d for d in win.ops if d.answer is not None]
        if versions is None:
            wrong = sum(d.answer != content(d) for d in kept)
        else:
            wrong = sum(_wrong_read(traffic, versions, d) for d in kept)
        numbers["reads_wrong"] = wrong + (not kept)
        notes.update(reads_checked=len(kept), read_bytes_checked=sum(len(d.answer) for d in kept))
    numbers["history_violations"] = history_violations(dss)
    return numbers, notes
