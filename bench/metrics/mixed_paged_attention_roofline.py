"""The mixed paged attention kernel's share of its roofline over the traced
steps: max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over the kernel's
summed device time. FLOPs and bytes are counted by ``bench.flops`` from each
traced step's segment layout, for every layer."""
from bench import flops

KERNEL = "mixed_paged_attention"


def read(run):
    tr = run.trace
    t = (tr or {}).get("kernel_s", {}).get(KERNEL, 0.0)
    if not t or not run.traced_steps:
        return None
    L, page = run.cfg["num_hidden_layers"], run.cfg["engine"]["kv_block"]
    f = b = 0.0
    for s in run.traced_steps:
        f += L * flops.attention_flops(run.cfg, s.q_lens, s.kv_lens)
        b += L * flops.attention_bytes(run.cfg, s.q_lens, s.kv_lens, page)
    if f == 0:
        return None
    return 100.0 * max(f / run.peaks["flops_bf16"], b / run.peaks["hbm_bytes_per_s"]) / t
