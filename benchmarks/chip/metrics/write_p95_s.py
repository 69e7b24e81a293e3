"""95th percentile of the wall latency of every write issued in the window."""
from chipbench.readers import p95_s


def read(r):
    return p95_s(r, "write")
