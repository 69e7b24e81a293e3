"""Percent of the bytes the kernel wrappers took that went in launches that
are one slice of a larger call: 100 x (``cdc_kernel.bytes_in_sliced`` +
``gf256.bytes_in_sliced``) / (``cdc_kernel.bytes_in`` + ``gf256.bytes_in``),
the program's counters over the traced window. A program that does not
slice has no such counters, and the metric is left out."""
from chipbench.spans import recording

SLICED = ("cdc_kernel.bytes_in_sliced", "gf256.bytes_in_sliced")
TAKEN = ("cdc_kernel.bytes_in", "gf256.bytes_in")


def read(r):
    rec = recording()
    if rec is None:
        return None
    counts = rec["counts"]
    taken = sum(counts.get(n, 0) for n in TAKEN)
    if not taken or not any(n in counts for n in SLICED):
        return None
    return 100.0 * sum(counts.get(n, 0) for n in SLICED) / taken
