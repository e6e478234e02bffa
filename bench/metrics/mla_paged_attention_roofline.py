"""The latent (MLA) mixed paged attention kernel's share of its roofline over
the traced steps: max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over the
device time of the packed step's ``attention`` scope, which in a latent
configuration holds ``mixed_paged_mla_attention`` and its q-block packing
(the absorbed projections are under ``qkv`` and ``o_proj``). FLOPs and bytes
are the configuration's reference module's counts (``bench.flops``:
absorbed scores over the latent and rope widths and latent values; latent
and rope pages, queries and outputs) from each traced step's segment
layout, for every layer. Reads ``run.scope_s`` rather than the kernel's
operation name, so that the benchmark's per-layer readers name no kernel
besides the mixed GQA kernel (``bench/tests/test_trace_reduce.py`` pins
that). The denominator therefore includes the q-block packing and
unpacking around the kernel (``_to_segments`` / ``_from_segments``, about
3% of the scope on a TPU v5e): moving that packing into or out of the scope
moves this share with no change to the kernel. Once that test allows
other names, ``KERNEL = "mixed_paged_mla_attention"`` and
``run.trace["kernel_s"]`` give the kernel's own time."""
from bench import flops


def read(run):
    t = (getattr(run, "scope_s", None) or {}).get("attention", 0.0)
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
