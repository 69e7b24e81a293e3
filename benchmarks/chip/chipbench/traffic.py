"""The one generator that turns a traffic file and a configuration into ops.

A traffic file (``traffic/<name>.json``) holds only parameters:

``object_sizes_mib``
    the sizes of the objects written or read, in equal shares.
``clients``
    the kinds of closed-loop client this mix runs, ``"write"`` or
    ``"read"``; the configuration's ``writers`` and ``readers`` say how
    many of each. Every read comes from a new client session, so no
    client-side cache of an earlier read answers it.
``preload_per_size``
    objects of each size written in set-up (reads choose among them).
``down_fragments``
    fragment indices whose holders crash after the preload (negative
    indices count from the end).
``check``
    what the correctness check samples (see ``check.py``).

Every slot cycles through the sizes in one fixed order, slot s starting s
places on, so that the slots start on different sizes. The seed only turns
the cycle (which slot starts where), so every seed runs the same sizes and
arrivals. (A seed that drew its own order per slot and round moved the
write p95 by 15-20% on a TPU v5e, and kept it there on a second run.) The
seed sets the content of every write, made fresh from the seed and the op's
index, which object of a size a read reads, and which reads the check
keeps.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Iterator

import numpy as np

MiB = 1 << 20
WRITE, READ, PRELOAD, CHECK = 1, 2, 3, 4
# the configuration's count of closed-loop clients of each kind
CLIENTS = {"write": "writers", "read": "readers"}


def seed_words(seed: int) -> int:
    return seed % (1 << 64)


def rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([seed_words(seed), *path])


def _mix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) % (1 << 64)
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) % (1 << 64)
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) % (1 << 64)
    return x ^ (x >> 31)


class Payloads:
    """Content for an op: a seeded pool of random words, read at an offset
    and XORed with a key, both drawn from the seed and the op's index."""

    def __init__(self, seed: int, max_size: int) -> None:
        self.seed = seed_words(seed)
        words = 2 * -(-max_size // 8)
        self.pool = np.random.Generator(np.random.PCG64([self.seed, 0])) \
            .bit_generator.random_raw(words)

    def make(self, size: int, *index: int) -> bytes:
        key = self.seed
        for i in index:
            key = _mix64(key ^ _mix64(int(i)))
        words = -(-size // 8)
        off = (key >> 11) % (self.pool.size - words + 1)
        return (self.pool[off:off + words] ^ np.uint64(key)).tobytes()[:size]


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
        self.sizes = [int(s * MiB) for s in params["object_sizes_mib"]]
        self.n = config["n_servers"]
        self.keep_share = float(params.get("check", {}).get("reads_kept_share", 0.0))
        self.payloads = Payloads(seed, max(self.sizes))

    # -- the deployment's shape ---------------------------------------------
    def slots(self, kind: str) -> int:
        return int(self.config[CLIENTS[kind]]) if kind in self.params["clients"] else 0

    def fragments(self, key: str) -> list[int]:
        return [i % self.n for i in self.params.get(key, [])]

    # -- op streams -----------------------------------------------------------
    def _sizes(self, slot: int) -> Iterator[int]:
        n = len(self.sizes)
        first = slot + seed_words(self.seed) % n
        for j in itertools.count():
            yield (first + j) % n

    def preload(self) -> list[OpSpec]:
        """Set-up writes: ``preload_per_size`` objects of each size."""
        per = int(self.params.get("preload_per_size", 0))
        return [OpSpec("write", f"p{s}.{i}", size, "", (PRELOAD, s, i))
                for i in range(per) for s, size in enumerate(self.sizes)]

    def stream(self, kind: str, slot: int) -> Iterator[OpSpec]:
        """Slot ``slot``'s ops of ``kind``, without end. A read is kept for
        the check with the chance ``reads_kept_share``, drawn from the seed,
        so the kept reads spread over the whole window."""
        pick = rng(self.seed, READ, slot, 1 << 20)
        keep = rng(self.seed, CHECK, slot)
        per = int(self.params.get("preload_per_size", 0))
        for j, s in enumerate(self._sizes(slot)):
            size = self.sizes[s]
            if kind == "write":
                yield OpSpec("write", f"w{slot}.{j}", size, f"writer{slot}",
                             (WRITE, slot, j))
                continue
            i = int(pick.integers(per))
            yield OpSpec("read", f"p{s}.{i}", size, f"reader{slot}.{j}",
                         (PRELOAD, s, i), bool(keep.random() < self.keep_share))

    def warmup(self) -> list[OpSpec]:
        """One op of each size on the window's own path: a write of a new
        object, or a read of a preloaded one."""
        if self.slots("write"):
            return [OpSpec("write", f"warm{s}", size, "writer0", (WRITE, 1 << 20, s))
                    for s, size in enumerate(self.sizes)]
        if self.slots("read"):
            return [OpSpec("read", f"p{s}.0", size, f"warm-reader{s}", (PRELOAD, s, 0))
                    for s, size in enumerate(self.sizes)]
        return []

    def payload(self, op: OpSpec) -> bytes:
        return self.payloads.make(op.size, *op.index)

    def sample(self, items: list, count: int, longest) -> list:
        """``count`` of ``items`` drawn from the seed, the longest among them."""
        if not items or count <= 0:
            return []
        top = max(items, key=longest)
        rest = [x for x in items if x is not top]
        picks = rng(self.seed, CHECK).permutation(len(rest))[: count - 1]
        return [top] + [rest[i] for i in sorted(picks)]
