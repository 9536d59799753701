"""Public op: chunked RG-LRU scan."""
from .. import interpret_mode
from .kernel import rglru_scan_pallas
from .ref import rglru_scan_ref  # noqa: F401


def rglru_scan(a, g, ct=128, br=512):
    b, t, r = a.shape
    ct = min(ct, t)
    while t % ct:
        ct -= 1
    br = min(br, r)
    while r % br:
        br -= 1
    return rglru_scan_pallas(a, g, ct=ct, br=br, interpret=interpret_mode())
