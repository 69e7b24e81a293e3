"""Protocol seconds per GiB done: time in op generator bodies and server
handlers, less the host time inside the kernels' entry points."""
from chipbench.readers import device_call_s, per_gib


def read(r):
    if r.protocol_s is None:
        return None
    return per_gib(r, r.protocol_s - device_call_s(r))
