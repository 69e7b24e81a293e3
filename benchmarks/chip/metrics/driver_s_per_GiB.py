"""Session and event-loop seconds per GiB done: the window's wall time
less the seconds in protocol code and the harness's own (making content)."""
from chipbench.readers import per_gib


def read(r):
    if r.protocol_s is None:
        return None
    return per_gib(r, r.window.seconds - r.protocol_s - r.window.harness_s)
