"""Host seconds computing and checking fragment CRC-32s (self time of
``ares.crc``, client and server side) per GiB done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("crc",))
