"""Share of its HBM roofline the CDC kernel reaches: the least time for
the bytes chunking needs over the summed device time of its events."""
from chipbench.readers import roofline_pct


def read(r):
    return roofline_pct(r, "cdc_gearhash")
