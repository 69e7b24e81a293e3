"""User MiB of every op completed in the window (bytes written and
acknowledged, bytes read back) over the window's wall seconds."""
from chipbench.readers import MiB, done_bytes


def read(r):
    b = done_bytes(r)
    return b / MiB / r.window.seconds if b else None
