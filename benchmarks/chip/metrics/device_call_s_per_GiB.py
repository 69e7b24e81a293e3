"""Host seconds inside the kernels' entry points (pad, transfer, launch,
wait, copy back, slice) per GiB done."""
from chipbench.readers import device_call_s, per_gib


def read(r):
    return per_gib(r, device_call_s(r)) if any(r.calls.values()) else None
