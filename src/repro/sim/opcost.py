"""Operation-level cost model: per-op FLOPs / HBM bytes for any ModelConfig.

Replaces the paper's Timeloop backend with closed-form op costs (LLM ops are
dense matmuls — the paper itself notes the mapping search space is trivial).
Operator fusion and FlashAttention are baked into the byte counts: fused
elementwise ops and softmax intermediates never touch HBM; attention streams
K/V exactly once (head-level tiling fits the 80MB compute buffer for every
config here — checked by ``fits_compute_buffer``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from repro.configs.base import LayerSpec, ModelConfig

# Page-granular swap pricing and prefix-cache fill savings shared with the
# engine's memory manager. The single source of truth lives in
# repro.memory.block_allocator (it describes how the allocator's pages round
# a token count, and what a skipped prefill never streams); re-exported here
# so sim pricing code keeps one import surface alongside kv_tokens_touched.
# Pricing skipped prefill through the scheduler is structural: a prefix-
# cache hit shrinks the StepPlan's prefill segments, so stage_ops never see
# the cached tokens — the sim skips their FLOPs and HBM fill bytes exactly
# where the engine skips their compute.
from repro.memory.block_allocator import (  # noqa: F401
    prefix_fill_bytes_saved,
    swap_bytes_block_rounded,
)
from repro.sim.hardware import Hardware

BYTES = 2  # fp16 inference (paper)


def kv_tokens_touched(ctx_lens: Sequence[int], block_size: int = 1) -> int:
    """KV tokens the ragged paged decode attention actually reads: each
    context rounds up to whole KV blocks (the kernel skips blocks past a
    row's length, so cost scales with real tokens — never with the padded
    cache extent). ``block_size=1`` is exact per-token pricing."""
    bs = max(block_size, 1)
    return sum(bs * -(-int(c) // bs) for c in ctx_lens)




@dataclasses.dataclass
class Op:
    name: str
    stage: str  # "prefill" | "decode" | "shared"
    matmuls: List[Tuple[int, int, int]]  # (m, k, n) on the systolic array
    weight_bytes: float = 0.0
    io_bytes: float = 0.0  # activation traffic that must hit HBM
    kv_bytes: float = 0.0  # prefetchable KV demand (decode attention)
    vu_flops: float = 0.0  # vector-unit work (softmax, scans)

    def compute_time(self, hw: Hardware) -> float:
        t = sum(hw.matmul_time(m, k, n) for (m, k, n) in self.matmuls)
        return t + self.vu_flops / hw.vu_flops

    def transfer_bytes(self, prefetched: float = 0.0) -> float:
        return self.weight_bytes + self.io_bytes + max(0.0, self.kv_bytes - prefetched)


# ---------------------------------------------------------------------------
# per-layer ops
# ---------------------------------------------------------------------------


def _attn_weight_bytes(cfg: ModelConfig) -> float:
    if cfg.mla:
        n = (
            cfg.mla_q_params
            + cfg.d_model * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + cfg.n_heads * cfg.v_head_dim * cfg.d_model
        )
    else:
        n = cfg.d_model * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
        n += cfg.n_heads * cfg.head_dim * cfg.d_model
    return n * BYTES


def _attn_qkvo_matmuls(cfg: ModelConfig, m: int) -> List[Tuple[int, int, int]]:
    if cfg.mla:
        qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        q = ([(m, cfg.d_model, cfg.q_lora_rank), (m, cfg.q_lora_rank, cfg.n_heads * qk_head)]
             if cfg.q_lora_rank else [(m, cfg.d_model, cfg.n_heads * qk_head)])
        return q + [
            (m, cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            (m, cfg.kv_lora_rank, cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            (m, cfg.n_heads * cfg.v_head_dim, cfg.d_model),
        ]
    hd = cfg.head_dim
    return [
        (m, cfg.d_model, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
        (m, cfg.n_heads * hd, cfg.d_model),
    ]


def _ffn_weight_bytes(cfg: ModelConfig, spec: LayerSpec, tokens: int) -> float:
    mult = 3 if cfg.glu else 2
    if spec.ffn == "dense":
        return mult * cfg.d_model * cfg.d_ff * BYTES
    if spec.ffn == "moe":
        active = min(cfg.n_experts, tokens * cfg.top_k)
        n = active * mult * cfg.d_model * cfg.moe_d_ff
        n += cfg.d_model * cfg.n_experts  # router
        if cfg.n_shared_experts:
            n += mult * cfg.d_model * cfg.shared_d_ff
        return n * BYTES
    return 0.0


def _ffn_matmuls(cfg: ModelConfig, spec: LayerSpec, m: int) -> List[Tuple[int, int, int]]:
    mult = 2 if cfg.glu else 1
    if spec.ffn == "dense":
        return [(m, cfg.d_model, mult * cfg.d_ff), (m, cfg.d_ff, cfg.d_model)]
    if spec.ffn == "moe":
        mm = [
            (m * cfg.top_k, cfg.d_model, mult * cfg.moe_d_ff),
            (m * cfg.top_k, cfg.moe_d_ff, cfg.d_model),
        ]
        if cfg.n_shared_experts:
            mm += [(m, cfg.d_model, mult * cfg.shared_d_ff), (m, cfg.shared_d_ff, cfg.d_model)]
        return mm
    return []


def _mamba_weight_bytes(cfg: ModelConfig, spec: LayerSpec) -> float:
    from repro.configs.base import ModelConfig as _MC  # param helpers live on cfg

    return cfg._mixer_params(spec) * BYTES


def layer_ops(
    cfg: ModelConfig,
    spec: LayerSpec,
    layer_name: str,
    n_p: int,  # prefill-chunk tokens this step
    prefill_ctx: int,  # context the chunk attends to (>= n_p with chunked prefill)
    n_d: int,  # decode tokens (batch of decode requests)
    kv_d: int,  # total decode KV tokens (sum of contexts)
    packed: bool,
    kv_block: int = 1,  # KV page size the paged kernel rounds reads up to
) -> List[Op]:
    """Ops of one layer in execution order (paper Fig 3 layer-by-layer)."""
    ops: List[Op] = []
    d = cfg.d_model
    mixer_is_attn = spec.mixer == "attn"

    def linear(name, matmul_fn, wbytes, act_k):
        """Emit linear ops.

        packed: the prefill chunk's op streams the weights; the decode tokens
        run an adjacent op with the SAME weights already on-chip (weight
        reuse — the paper's packing), paying only their small-matmul compute.
        serial: each stage streams the weights itself.
        """
        if packed and n_p and n_d:
            ops.append(Op(name + "/p", "prefill", matmul_fn(n_p), wbytes,
                          io_bytes=n_p * act_k * BYTES))
            ops.append(Op(name + "/d", "decode", matmul_fn(n_d), 0.0,
                          io_bytes=n_d * act_k * BYTES))
        else:
            if n_p:
                ops.append(Op(name + "/p", "prefill", matmul_fn(n_p), wbytes,
                              io_bytes=n_p * act_k * BYTES))
            if n_d:
                ops.append(Op(name + "/d", "decode", matmul_fn(n_d), wbytes,
                              io_bytes=n_d * act_k * BYTES))

    if mixer_is_attn:
        linear(f"{layer_name}.qkvo", lambda m: _attn_qkvo_matmuls(cfg, m),
               _attn_weight_bytes(cfg), 2 * d)
        hd_q = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) if cfg.mla else cfg.head_dim
        hd_v = cfg.v_head_dim if cfg.mla else cfg.head_dim
        H = cfg.n_heads
        if n_p:
            # FlashAttention prefill: causal, ~ctx/2 average span; K/V streamed once
            span = (prefill_ctx + max(prefill_ctx - n_p, 0)) / 2.0
            mm = [(n_p, hd_q, int(span) or 1), (n_p, int(span) or 1, hd_v)]
            # unified mixed-batch kernel: the chunk reads its prefix+chunk KV
            # ONCE, rounded up to whole pages (never once per chunk token),
            # plus the chunk's own KV append — the same block-rounded bytes
            # the engine's kernel streams
            ctx_read = kv_tokens_touched([prefill_ctx], kv_block)
            ops.append(Op(f"{layer_name}.attn/p", "prefill",
                          [(m * H, k, n) for (m, k, n) in [mm[0]]] + [(mm[1][0] * H, mm[1][1], mm[1][2])],
                          weight_bytes=0.0,
                          io_bytes=(ctx_read + n_p) * cfg.kv_bytes_per_token_layer,
                          vu_flops=6.0 * H * n_p * span))
        if n_d:
            # decode attention: heads batch into MXU rows (m = n_d*H)
            per = max(kv_d // max(n_d, 1), 1)
            if cfg.mla:
                L = cfg.kv_lora_rank + cfg.qk_rope_head_dim
                mm = [(n_d * H, L, per), (n_d * H, per, cfg.kv_lora_rank)]
            else:
                mm = [(n_d * H, cfg.head_dim, per), (n_d * H, per, cfg.head_dim)]
            ops.append(Op(f"{layer_name}.attn/d", "decode", mm,
                          weight_bytes=0.0,
                          kv_bytes=kv_d * cfg.kv_bytes_per_token_layer,
                          io_bytes=n_d * cfg.kv_bytes_per_token_layer,  # KV append
                          vu_flops=6.0 * H * kv_d))
    else:
        wb = _mamba_weight_bytes(cfg, spec)
        d_in = cfg.m_expand * d
        if packed and n_p and n_d:
            ops.append(Op(f"{layer_name}.ssm/p", "prefill",
                          [(n_p, d, 2 * d_in), (n_p, d_in, d)], wb,
                          io_bytes=n_p * 2 * d * BYTES,
                          vu_flops=20.0 * n_p * d_in * max(cfg.m_d_state, cfg.m_d_state_m1)))
            ops.append(Op(f"{layer_name}.ssm/d", "decode",
                          [(n_d, d, 2 * d_in), (n_d, d_in, d)], 0.0,
                          io_bytes=n_d * 2 * d * BYTES,
                          vu_flops=20.0 * n_d * d_in * max(cfg.m_d_state, cfg.m_d_state_m1)))
        else:
            if n_p:
                ops.append(Op(f"{layer_name}.ssm/p", "prefill",
                              [(n_p, d, 2 * d_in), (n_p, d_in, d)], wb,
                              io_bytes=n_p * 2 * d * BYTES,
                              vu_flops=20.0 * n_p * d_in * max(cfg.m_d_state, cfg.m_d_state_m1)))
            if n_d:
                ops.append(Op(f"{layer_name}.ssm/d", "decode",
                              [(n_d, d, 2 * d_in), (n_d, d_in, d)], wb,
                              io_bytes=n_d * 2 * d * BYTES,
                              vu_flops=20.0 * n_d * d_in * max(cfg.m_d_state, cfg.m_d_state_m1)))

    if spec.ffn != "none":
        linear(f"{layer_name}.ffn", lambda m: _ffn_matmuls(cfg, spec, m),
               _ffn_weight_bytes(cfg, spec, (n_p + n_d) if packed else max(n_p, n_d)),
               2 * d)
    return ops


def stage_ops(
    cfg: ModelConfig,
    n_p: int,
    prefill_ctx: int,
    n_d: int,
    kv_d: int,
    packed: bool,
    kv_block: int = 1,
) -> List[Op]:
    """Full model step: embed + all layers + LM head.

    serial (packed=False): prefill ops for all layers first, then decode ops —
    matching the paper's sequential baseline.
    packed: layer-by-layer with merged linear ops.
    """
    ops: List[Op] = []
    V, d = cfg.vocab_size, cfg.d_model

    def head(m, stage):
        return Op(f"head/{stage[0]}", stage, [(m, d, V)], weight_bytes=V * d * BYTES,
                  io_bytes=m * d * BYTES)

    def embed(m, stage):
        return Op(f"embed/{stage[0]}", stage, [], weight_bytes=0.0,
                  io_bytes=m * d * BYTES)

    if packed:
        if n_p:
            ops.append(embed(n_p, "prefill"))
        if n_d:
            ops.append(embed(n_d, "decode"))
        for i, spec in enumerate(cfg.layer_specs):
            ops.extend(layer_ops(cfg, spec, f"L{i}", n_p, prefill_ctx, n_d, kv_d, True,
                                  kv_block=kv_block))
        # head: prefill needs only its last token's logits; decode tokens ride
        # the same weights (packed -> zero weight traffic for the decode op)
        if n_p:
            ops.append(head(1, "prefill"))
        if n_d:
            h = head(n_d, "decode")
            if n_p:
                h.weight_bytes = 0.0
            ops.append(h)
    else:
        if n_p:
            ops.append(embed(n_p, "prefill"))
            for i, spec in enumerate(cfg.layer_specs):
                ops.extend(layer_ops(cfg, spec, f"L{i}", n_p, prefill_ctx, 0, 0, False,
                                      kv_block=kv_block))
            ops.append(head(1, "prefill"))
        if n_d:
            ops.append(embed(n_d, "decode"))
            for i, spec in enumerate(cfg.layer_specs):
                ops.extend(layer_ops(cfg, spec, f"L{i}", 0, 0, n_d, kv_d, False))
            ops.append(head(n_d, "decode"))
    return ops


def fits_compute_buffer(cfg: ModelConfig, hw: Hardware, block_tokens: int = 512) -> bool:
    """FlashAttention head/block tiling working set vs the 80MB compute buffer."""
    hd = cfg.head_dim if not cfg.mla else (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    per_head_block = (2 * block_tokens * hd + block_tokens * block_tokens) * BYTES
    return 2 * per_head_block < hw.compute_buffer  # double-buffered
