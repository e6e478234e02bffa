"""Operations and bytes a served step needs, from the configuration's widths
and the step's segment layout (``StepPlan.cu_q_lens`` / ``kv_lens``).

Counts are of the algorithm, not of what a kernel happens to do: a multiply-
add is 2 FLOPs, causal attention counts only keys at or before each query,
bytes are the K/V pages a segment must read (whole pages of ``page`` tokens)
plus its queries and outputs, in bf16.
"""
from __future__ import annotations

from typing import Sequence


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return (d, H, cfg["num_key_value_heads"], cfg.get("head_dim", d // H),
            cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies in one layer (projections + SwiGLU FFN)."""
    d, H, KV, hd, ff, _, _ = _dims(cfg)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def segment_keys(q_len: int, kv_len: int) -> int:
    """Query-key pairs of a causal segment: its q_len queries are the last
    q_len positions of a kv_len context."""
    first = kv_len - q_len + 1
    return q_len * (first + kv_len) // 2


def attention_flops(cfg: dict, q_lens: Sequence[int], kv_lens: Sequence[int]) -> float:
    """One layer's attention FLOPs for a step's segments: QK^T and PV, each
    2 * head_dim per query head per key."""
    _, H, _, hd, _, _, _ = _dims(cfg)
    keys = sum(segment_keys(q, k) for q, k in zip(q_lens, kv_lens) if q > 0)
    return 4.0 * H * hd * keys


def attention_bytes(cfg: dict, q_lens: Sequence[int], kv_lens: Sequence[int],
                    page: int) -> float:
    """One layer's attention bytes: each segment's K and V pages (every KV
    head, whole pages), its queries read and its outputs written (bf16)."""
    _, H, KV, hd, _, _, _ = _dims(cfg)
    pages = sum(-(-k // page) for q, k in zip(q_lens, kv_lens) if q > 0)
    rows = sum(q_lens)
    return 2.0 * (2 * pages * page * KV * hd + 2 * rows * H * hd)


def step_flops(cfg: dict, q_lens: Sequence[int], kv_lens: Sequence[int],
               sampled: int) -> float:
    """Useful FLOPs of one served step: every real row through every layer's
    matmuls, causal attention, and the LM head for the sampled rows only."""
    d, _, _, _, _, V, L = _dims(cfg)
    rows = sum(q_lens)
    return L * (2.0 * layer_matmul_params(cfg) * rows
                + attention_flops(cfg, q_lens, kv_lens)) + 2.0 * d * V * sampled
