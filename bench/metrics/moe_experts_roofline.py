"""The routed experts' grouped matmuls' share of their roofline over the
traced steps: max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over the
device time of the packed step's ``moe_experts`` scope, which holds only
the grouped matmuls (``moe_grouped_matmul``: gate and up, then down).

FLOPs: each real row of a traced step goes to ``num_experts_per_tok``
experts in every MoE layer, and each such routed row costs 2 x 3 x hidden x
expert width (gate, up and down). Bytes: the weights of the experts hit
(3 x hidden x expert width in bf16 each), plus each routed row read and
written by the three products (3 x (hidden + expert width) bf16 values).
Experts hit per step is a window mean, not the traced steps' own:
``moe.experts_hit`` (experts with a routed row, summed over MoE layers and
steps) over the window's ``engine.step`` calls, times the traced steps.

Widths are the configuration file's own keys (``hidden_size``,
``moe_intermediate_size``, ``num_experts_per_tok``); MoE layers are counted
from its keys (``moe_layers``), so the reader serves any MoE configuration."""


def moe_layers(cfg: dict) -> int:
    """Layers with routed experts: past ``first_k_dense_replace`` and on
    ``moe_layer_freq`` (DeepSeek), or every ``decoder_sparse_step``-th
    outside ``mlp_only_layers`` (Qwen-MoE); every layer where neither says."""
    first, freq = cfg.get("first_k_dense_replace") or 0, cfg.get("moe_layer_freq") or 1
    step, dense = cfg.get("decoder_sparse_step") or 1, set(cfg.get("mlp_only_layers") or ())
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i >= first and i % freq == 0 and (i + 1) % step == 0 and i not in dense)


def read(run):
    t = (getattr(run, "scope_s", None) or {}).get("moe_experts", 0.0)
    steps = run.span_n.get("engine.step", 0)
    hit = run.counters.get("moe.experts_hit")
    if not t or not run.traced_steps or not steps or hit is None:
        return None
    cfg = run.cfg
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed = (cfg["num_experts_per_tok"] * moe_layers(cfg)
              * sum(sum(s.q_lens) for s in run.traced_steps))
    f = 2.0 * 3 * d * fe * routed
    b = 2.0 * (3 * d * fe * hit / steps * len(run.traced_steps) + 3 * (d + fe) * routed)
    if f == 0:
        return None
    return 100.0 * max(f / run.peaks["flops_bf16"], b / run.peaks["hbm_bytes_per_s"]) / t
