"""Host seconds copying bytes into coding rows and rows back into fragment
bytes or values (self time of ``ares.rs.pack`` and ``ares.rs.unpack``) per
GiB done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("rs.pack", "rs.unpack"))
