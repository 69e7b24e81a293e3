"""Host seconds sizing messages in their wire frames (self time of
``ares.frame``) per GiB done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("frame",))
