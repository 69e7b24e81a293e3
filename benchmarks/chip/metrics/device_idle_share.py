"""Percent of the traced window in which no operation ran on the device."""
from chipbench.readers import idle_pct


def read(r):
    return idle_pct(r)
