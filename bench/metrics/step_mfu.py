"""Useful model FLOPs of the traced steps over the traced window times the
chip's bf16 peak: 2 x layer weights x real rows, causal attention, and the
LM head for sampled rows only (``bench.flops.step_flops``)."""
from bench import flops


def read(run):
    tr = run.trace
    if not tr or not run.traced_steps or tr["window_s"] <= 0:
        return None
    f = sum(flops.step_flops(run.cfg, s.q_lens, s.kv_lens, s.sampled)
            for s in run.traced_steps)
    return 100.0 * f / (tr["window_s"] * run.peaks["flops_bf16"]) if f else None
