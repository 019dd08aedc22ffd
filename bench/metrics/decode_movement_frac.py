"""Model step: share of the decode program's device time in ops under
none of its compute scopes (``scopes.COMPUTE``, the program's
``DECODE_SCOPES``): the layer scan's slices of the KV pool and of the
stacked weights, the pool's write-back, and XLA's copies."""
from scopes import COMPUTE


def read(rec):
    t = rec.get("trace") or {}
    s = t.get("scope_s")
    if not s or not sum(s.values()):
        return None
    return sum(v for k, v in s.items() if k not in COMPUTE) / sum(s.values())
