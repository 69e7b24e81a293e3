"""The one generator that turns a traffic file and a configuration into ops.

A traffic file (``traffic/<name>.json``) holds only parameters:

``object_sizes_mib``
    the sizes of the objects written or read, in equal shares.
``file_sizes_mib``
    in place of ``object_sizes_mib``, for a mix that edits: each writer
    slot owns one file of each size, preloaded by its own session.
``clients``
    the kinds of closed-loop client this mix runs: ``"write"`` (a new
    object per op), ``"edit"`` (one byte of an owned file flipped per op,
    the whole new content saved) or ``"read"``; the configuration's
    ``writers`` (for both kinds of writer) and ``readers`` say how many of
    each. Every read comes from a new client session, so no client-side
    cache of an earlier read answers it. Where the mix edits, a read reads
    one of the edited files.
``preload_per_size``
    objects of each size written in set-up (reads choose among them).
``down_fragments``
    fragment indices whose holders crash after the preload (negative
    indices count from the end).
``warm_coding``
    set-up also compiles the GF(256) kernel at the shapes the window's ops
    may take that the warm-up ops need not reach (see
    ``harness._warm_coding``): ``encode_blocks``, the most changed blocks
    an edit encodes; ``decode_groups``, the most fragment index sets one
    read fuses.
``check``
    what the correctness check samples (see ``check.py``).

Every slot cycles through the sizes in one fixed order, slot s starting s
places on, so that the slots start on different sizes. Neither the order
nor the simulated network's delays (``harness.NETWORK_SEED``) follow the
seed: the store steps a virtual-time network, so sizes, order and delays
fix which ops overlap, and with that the wall latency of each. Seeds then
replay one schedule of writes, to a block header's wire time: on the WAN
through the whole window, on a LAN until such a difference parts a near
tie. (On a TPU v5e a seed that drew its own order per slot and round moved
the write p95 by 15-20%, and one that turned the cycle and drew the delays
by 10%, each the same again on a second run.) The seed
sets the content of every write, made fresh from the seed and the op's
index, which object of a size a read reads, and which reads the check
keeps.

Version v + 1 of an edited file is version v with the byte at a position
drawn from the seed, the file and v + 1 XORed with 0xFF; version 0 is made
from the seed and the file, as any payload. So ``version`` rebuilds any
version from the seed and the check keeps no copies.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Iterator

import numpy as np

MiB = 1 << 20
WRITE, READ, PRELOAD, CHECK, EDIT = 1, 2, 3, 4, 5
# the configuration's count of closed-loop clients of each kind
CLIENTS = {"write": "writers", "edit": "writers", "read": "readers"}
# the writer slot whose files the warm-up edits, once each
WARM_SLOT = 0


def seed_words(seed: int) -> int:
    return seed % (1 << 64)


def rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([seed_words(seed), *path])


def _mix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) % (1 << 64)
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) % (1 << 64)
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) % (1 << 64)
    return x ^ (x >> 31)


def _key(seed: int, *index: int) -> int:
    key = seed
    for i in index:
        key = _mix64(key ^ _mix64(int(i)))
    return key


class Payloads:
    """Content for an op: a seeded pool of random words, read at an offset
    and XORed with a key, both drawn from the seed and the op's index."""

    def __init__(self, seed: int, max_size: int) -> None:
        self.seed = seed_words(seed)
        words = 2 * -(-max_size // 8)
        self.pool = np.random.Generator(np.random.PCG64([self.seed, 0])) \
            .bit_generator.random_raw(words)

    def array(self, size: int, *index: int) -> np.ndarray:
        """``make``'s content as a fresh uint8 array."""
        key = _key(self.seed, *index)
        words = -(-size // 8)
        off = (key >> 11) % (self.pool.size - words + 1)
        return (self.pool[off:off + words] ^ np.uint64(key)).view(np.uint8)[:size]

    def make(self, size: int, *index: int) -> bytes:
        return self.array(size, *index).tobytes()


@dataclass
class OpSpec:
    kind: str          # "write" | "read"
    fid: str
    size: int
    session: str       # client id that issues it
    index: tuple       # payload index of the content written or read
    keep: bool = False  # keep the answer for the check


class Traffic:
    def __init__(self, params: dict, config: dict, seed: int) -> None:
        self.params = params
        self.config = config
        self.seed = seed
        self.edits = "edit" in params["clients"]
        sizes = params["file_sizes_mib" if self.edits else "object_sizes_mib"]
        self.sizes = [int(s * MiB) for s in sizes]
        self.n = config["n_servers"]
        self.keep_share = float(params.get("check", {}).get("reads_kept_share", 0.0))
        self.payloads = Payloads(seed, max(self.sizes))
        self._saved: dict[tuple, tuple[int, bytearray]] = {}  # file -> its last version

    # -- the deployment's shape ---------------------------------------------
    def slots(self, kind: str) -> int:
        return int(self.config[CLIENTS[kind]]) if kind in self.params["clients"] else 0

    def fragments(self, key: str) -> list[int]:
        return [i % self.n for i in self.params.get(key, [])]

    # -- op streams -----------------------------------------------------------
    def _sizes(self, slot: int) -> Iterator[int]:
        n = len(self.sizes)
        for j in itertools.count():
            yield (slot + j) % n

    def preload(self) -> list[OpSpec]:
        """Set-up writes: ``preload_per_size`` objects of each size; where
        the mix edits, version 0 of every file, written by its owner's
        session (the files of slot i are the i-th of every ``writers``)."""
        if self.edits:
            return [self._edit(slot, s, 0) for s in range(len(self.sizes))
                    for slot in range(self.slots("edit"))]
        per = int(self.params.get("preload_per_size", 0))
        return [OpSpec("write", f"p{s}.{i}", size, "", (PRELOAD, s, i))
                for i in range(per) for s, size in enumerate(self.sizes)]

    def stream(self, kind: str, slot: int) -> Iterator[OpSpec]:
        """Slot ``slot``'s ops of ``kind``, without end. A read is kept for
        the check with the chance ``reads_kept_share``, drawn from the seed,
        so the kept reads spread over the whole window. An edit slot saves
        the next version of each of its files in turn; where the mix edits,
        a read reads the file of its size that an owner drawn from the seed
        edits."""
        pick = rng(self.seed, READ, slot, 1 << 20)
        keep = rng(self.seed, CHECK, slot)
        per = int(self.params.get("preload_per_size", 0))
        n = len(self.sizes)
        for j, s in enumerate(self._sizes(slot)):
            size = self.sizes[s]
            if kind == "edit":
                yield self._edit(slot, s, self.first_version(slot) + j // n + 1)
                continue
            if self.edits:  # a read of one of the edited files
                owner = int(pick.integers(self.slots("edit")))
                yield OpSpec("read", self.file(owner, s), size, f"reader{slot}.{j}",
                             (EDIT, owner, s), bool(keep.random() < self.keep_share))
                continue
            if kind == "write":
                yield OpSpec("write", f"w{slot}.{j}", size, f"writer{slot}",
                             (WRITE, slot, j))
                continue
            i = int(pick.integers(per))
            yield OpSpec("read", f"p{s}.{i}", size, f"reader{slot}.{j}",
                         (PRELOAD, s, i), bool(keep.random() < self.keep_share))

    def warmup(self) -> list[OpSpec]:
        """One op of each size on the window's own path: a write of a new
        object, or a read of a preloaded one; where the mix edits, an edit
        of each of ``WARM_SLOT``'s files and a read of each."""
        if self.edits:
            return [self._edit(WARM_SLOT, s, 1) for s in range(len(self.sizes))] + [
                OpSpec("read", self.file(WARM_SLOT, s), size, f"warm-reader{s}",
                       (EDIT, WARM_SLOT, s)) for s, size in enumerate(self.sizes)]
        if self.slots("write"):
            return [OpSpec("write", f"warm{s}", size, "writer0", (WRITE, 1 << 20, s))
                    for s, size in enumerate(self.sizes)]
        if self.slots("read"):
            return [OpSpec("read", f"p{s}.0", size, f"warm-reader{s}", (PRELOAD, s, 0))
                    for s, size in enumerate(self.sizes)]
        return []

    def payload(self, op: OpSpec) -> bytes:
        if op.index[0] == EDIT:
            return self._save(*op.index[1:])
        return self.payloads.make(op.size, *op.index)

    # -- edited files -----------------------------------------------------------
    @staticmethod
    def file(slot: int, s: int) -> str:
        return f"e{slot}.{s}"

    def _edit(self, slot: int, s: int, v: int) -> OpSpec:
        return OpSpec("write", self.file(slot, s), self.sizes[s], f"writer{slot}",
                      (EDIT, slot, s, v))

    def first_version(self, slot: int) -> int:
        """The version of the slot's files when the window opens (the
        warm-up edits ``WARM_SLOT``'s files once)."""
        return int(slot == WARM_SLOT)

    def flip_at(self, slot: int, s: int, v: int) -> int:
        """The byte that version ``v`` flips."""
        return _key(self.payloads.seed, EDIT, slot, s, v) % self.sizes[s]

    def _save(self, slot: int, s: int, v: int) -> bytes:
        """Version ``v`` for its writer: the writer's last version with one
        more byte flipped, so a save costs one copy and not a rebuild; the
        bytes ``version`` gives."""
        held = self._saved.get((slot, s))
        if held is not None and held[0] == v - 1:
            buf = held[1]
            buf[self.flip_at(slot, s, v)] ^= 0xFF
        else:
            buf = bytearray(self.version(slot, s, v))
        self._saved[(slot, s)] = (v, buf)
        return bytes(buf)

    def version(self, slot: int, s: int, v: int) -> bytes:
        """Version ``v`` of file ``(slot, s)``, rebuilt from the seed."""
        data = self.payloads.array(self.sizes[s], EDIT, slot, s)
        np.bitwise_xor.at(data, [self.flip_at(slot, s, i) for i in range(1, v + 1)], 0xFF)
        return data.tobytes()

    def sample(self, items: list, count: int, longest) -> list:
        """``count`` of ``items`` drawn from the seed, the longest among them."""
        if not items or count <= 0:
            return []
        top = max(items, key=longest)
        rest = [x for x in items if x is not top]
        picks = rng(self.seed, CHECK).permutation(len(rest))[: count - 1]
        return [top] + [rest[i] for i in sorted(picks)]
