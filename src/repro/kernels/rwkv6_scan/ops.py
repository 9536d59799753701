"""Public op: chunked RWKV6 recurrence."""
from .. import interpret_mode
from .kernel import rwkv6_scan_pallas
from .ref import rwkv6_scan_ref  # noqa: F401


def rwkv6_scan(r, k, v, w, u, ct=64):
    t = r.shape[2]
    ct = min(ct, t)
    while t % ct:
        ct -= 1
    return rwkv6_scan_pallas(r, k, v, w, u, ct=ct, interpret=interpret_mode())
