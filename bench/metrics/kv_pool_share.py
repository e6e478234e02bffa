"""Share of the device's busy time in the traced window spent under the
packed step's ``kv_pool`` scope: a layer's slice out of the scanned KV pool,
the scatter of the step's K/V, the write-back, and the relayout copies the
compiler puts around them. Reads ``run.scope_s`` (``bench.trace_scopes.
scope_seconds`` with ``Engine.step_op_scopes``). None without a trace or
scopes, or where the operations of the step that no scope names, or that
different step shapes name differently, exceed a tenth of the step's
device time."""
from bench.trace_scopes import AMBIGUOUS, STEP_SCOPES, UNSCOPED

MAX_UNNAMED = 0.10


def read(run):
    s, tr = getattr(run, "scope_s", None), run.trace
    if not s or not tr or tr["busy_s"] <= 0:
        return None
    unnamed = s.get(UNSCOPED, 0.0) + s.get(AMBIGUOUS, 0.0)
    step = unnamed + sum(s.get(k, 0.0) for k in STEP_SCOPES)
    if step <= 0 or unnamed > MAX_UNNAMED * step:
        return None
    return 100.0 * s.get("kv_pool", 0.0) / tr["busy_s"]
