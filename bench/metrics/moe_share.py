"""Share of the device's busy time in the traced window spent under the
packed step's MoE scopes: ``moe_route`` (router, top-k, the tile-aligned
layout of the routed rows, their gather and the weighted sum back) and
``moe_experts`` (the grouped expert matmuls); the shared experts count
under ``mlp``. Reads ``run.scope_s``. None without a trace or scopes, or
where the operations of the step that no scope names, or that different
step shapes name differently, exceed a tenth of the step's device time
(``kv_pool_share``'s guard)."""
from bench.trace_scopes import AMBIGUOUS, STEP_SCOPES, UNSCOPED

MOE_SCOPES = ("moe_route", "moe_experts")
MAX_UNNAMED = 0.10


def read(run):
    s, tr = getattr(run, "scope_s", None), run.trace
    if not s or not tr or tr["busy_s"] <= 0:
        return None
    unnamed = s.get(UNSCOPED, 0.0) + s.get(AMBIGUOUS, 0.0)
    step = unnamed + sum(s.get(k, 0.0) for k in STEP_SCOPES + MOE_SCOPES)
    if step <= 0 or unnamed > MAX_UNNAMED * step:
        return None
    return 100.0 * sum(s.get(k, 0.0) for k in MOE_SCOPES) / tr["busy_s"]
