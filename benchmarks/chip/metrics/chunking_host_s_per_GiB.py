"""Host seconds of block identification outside the CDC kernel call: the
min/max walk and chunk slicing (self time of ``ares.cdc``) and the block
hashes and matching (``ares.block_hash``) per GiB done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("cdc", "block_hash"))
