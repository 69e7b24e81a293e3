"""Host seconds the kernel wrappers spend zero-padding operands to their
width bucket and slicing results back (self time of ``ares.gf256.pad``,
``ares.gf256.slice`` and ``ares.cdc_kernel.pad``) per GiB done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("gf256.pad", "gf256.slice", "cdc_kernel.pad"))
