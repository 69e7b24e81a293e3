"""Percent of the bytes the kernel wrappers sent to the device that are
zero padding to the width bucket (the wrappers' ``*.bytes_in`` and
``*.bytes_in_padded`` counters)."""
from chipbench.spans import padding_pct


def read(r):
    return padding_pct()
