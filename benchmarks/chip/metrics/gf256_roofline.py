"""Share of its HBM roofline the GF(256) kernel reaches: the least time
for the bytes the RS operations need over the summed device time of its
events."""
from chipbench.readers import roofline_pct


def read(r):
    return roofline_pct(r, "gf256_matmul")
