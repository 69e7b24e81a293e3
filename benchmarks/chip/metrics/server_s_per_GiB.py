"""Host seconds in the servers' own handler code (self time of
``ares.handle``: what it calls, such as a CRC check, is apart) per GiB
done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("handle",))
