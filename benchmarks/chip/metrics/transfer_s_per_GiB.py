"""Host seconds the kernel wrappers spend moving operands to the device
and results back, waits included (self time of ``ares.*.put`` and
``ares.*.get``) per GiB done."""
from chipbench.spans import self_s_per_gib


def read(r):
    return self_s_per_gib(r, ("gf256.put", "gf256.get", "cdc_kernel.put", "cdc_kernel.get"))
