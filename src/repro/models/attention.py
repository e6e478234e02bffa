"""Attention mixers: GQA (full/local, qk-norm, bias, softcap) and MLA.

Two entry modes via one function:
  * ``cache=None``  — full-sequence causal self-attention (training / one-shot
    prefill without cache).
  * ``cache`` given — write this call's K/V (or MLA latent) into the cache at
    ``cache_index`` and attend against positions ``<= q_pos``. This single
    path serves chunked prefill (T = chunk len) and decode (T = 1) — exactly
    the packed execution model of the paper.

The XLA path below is the reference; the Pallas kernels in ``repro.kernels``
implement the same math for the TPU hot paths and are validated against
``repro.kernels.ref`` which mirrors these equations.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.layers import (
    apply_rope,
    dense,
    dense_init,
    rms_norm,
    rms_norm_init,
    softcap,
    yarn_mscale,
)

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def attn_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    if cfg.mla:
        return _mla_init(rng, cfg, dtype)
    ks = jax.random.split(rng, 6)
    d, hd = cfg.d_model, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd)
        p["k_norm"] = rms_norm_init(hd)
    return p


def _mla_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if cfg.q_lora_rank:  # low-rank query through a normed latent
        q = {"q_down": dense_init(ks[0], d, cfg.q_lora_rank, dtype=dtype),
             "q_norm": rms_norm_init(cfg.q_lora_rank),
             "q_up": dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_head,
                                dtype=dtype)}
    else:  # q_lora_rank null (DeepSeek-V2-Lite): one direct projection
        q = {"wq": dense_init(ks[0], d, cfg.n_heads * qk_head, dtype=dtype)}
    return {
        **q,
        "kv_down": dense_init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                              dtype=dtype),
        "kv_norm": rms_norm_init(cfg.kv_lora_rank),
        "kv_up": dense_init(
            ks[3], cfg.kv_lora_rank, cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            dtype=dtype,
        ),
        "wo": dense_init(ks[4], cfg.n_heads * cfg.v_head_dim, d, dtype=dtype),
    }


def kv_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Zeroed per-layer KV cache (GQA) or latent cache (MLA)."""
    if cfg.mla:
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, window: Optional[int]):
    """(B, 1, T, S) additive bias: causal (+ sliding window)."""
    ok = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
    if window is not None:
        ok &= k_pos[None, None, None, :] > q_pos[:, None, :, None] - window
    return jnp.where(ok, 0.0, NEG_INF)


def cache_write(buf, new, index):
    """Write ``new`` (B,T,...) into ``buf`` (B,S,...) at sequence offset(s).

    ``index`` is a scalar (uniform offset — dry-run / simple serving) or a
    (B,) vector (per-request offsets — continuous batching).
    """
    new = new.astype(buf.dtype)
    index = jnp.asarray(index)
    if index.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, index, axis=1)
    return jax.vmap(
        lambda b, n, i: jax.lax.dynamic_update_slice_in_dim(b, n, i, axis=0)
    )(buf, new, index)


FLASH_THRESHOLD = 1 << 22  # T*S above this routes to the blocked flash path


def _attend(q, k, v, q_pos, k_pos_len, window, scale, cap, causal=True):
    """Dispatch: blocked flash (large T*S) vs direct sdpa (small/exact-test path).

    q: (B,T,H,hd); k/v: (B,S,KV,hd); q_pos: (B,T); keys at positions 0..S-1.
    """
    from repro.models.flash_xla import flash_sdpa

    T, S = q.shape[1], k.shape[1]
    if T > 1 and T * S > FLASH_THRESHOLD:
        return flash_sdpa(
            q, (k, v), q_pos, jnp.arange(S, dtype=jnp.int32),
            scale=scale, window=window, softcap=cap, causal=causal,
        )
    if causal:
        bias = _mask_bias(q_pos, jnp.arange(S), window)
    else:
        bias = jnp.zeros((q.shape[0], 1, T, S), q.dtype)
    return _sdpa(q, k, v, bias, scale, cap)


def _sdpa(q, k, v, bias, scale, cap):
    """q: (B,T,H,hd) k/v: (B,S,KV,hd) grouped-query attention core (fp32 softmax)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, T, KV, G, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32) * scale
    scores = softcap(scores, cap)
    # bias (B,1,T,S) -> (B,1,1,T,S) so it broadcasts over (kv, group)
    scores = scores + bias.astype(jnp.float32)[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, T, H, v.shape[-1])  # v head dim may differ from q (MLA)


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------


def attn_apply(
    params,
    cfg: ModelConfig,
    spec: LayerSpec,
    x,
    positions,
    inv_freq,
    *,
    cache=None,
    cache_index=None,
):
    if cfg.mla:
        return _mla_apply(params, cfg, x, positions, inv_freq, cache=cache, cache_index=cache_index)
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = dense(params["wq"], x).reshape(B, T, cfg.n_heads, hd)
    k = dense(params["wk"], x).reshape(B, T, cfg.n_kv_heads, hd)
    v = dense(params["wv"], x).reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)

    window = cfg.local_window if spec.attn_kind == "local" else None
    scale = 1.0 / (hd**0.5)

    if cache is None:
        out = _attend(q, k, v, positions, T, window, scale, cfg.attn_logit_softcap)
        new_cache = None
    else:
        cache = {
            "k": cache_write(cache["k"], k, cache_index),
            "v": cache_write(cache["v"], v, cache_index),
        }
        out = None
        if T == 1 and cfg.sp_decode:
            from repro.distributed import ctx
            from repro.distributed.sharding import dp_axes
            from repro.distributed.sp_attention import sp_decode_attention

            mesh = ctx.activation_mesh()
            # batch=1: the data axis carries the KV sequence (long_500k);
            # batched decode: batch stays on data, sequence shards over model
            if mesh is not None:
                axis = "data" if B == 1 else "model"
                b_axes = None if B == 1 else dp_axes(mesh)
                if axis in mesh.axis_names and cache["k"].shape[1] % mesh.shape[axis] == 0:
                    lengths = positions[:, 0] + 1
                    out = sp_decode_attention(
                        q, cache["k"].astype(x.dtype), cache["v"].astype(x.dtype),
                        lengths, mesh, axis=axis, batch_axes=b_axes,
                        window=window, softcap=cfg.attn_logit_softcap,
                    )
        if out is None:
            out = _attend(
                q, cache["k"].astype(x.dtype), cache["v"].astype(x.dtype),
                positions, cache["k"].shape[1], window, scale, cfg.attn_logit_softcap,
            )
        new_cache = cache
    y = dense(params["wo"], out.reshape(B, T, cfg.n_heads * hd))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA apply (direct for full-seq; absorbed for cached/decode)
# ---------------------------------------------------------------------------


def _mla_qkv_rope(params, cfg, x, positions, inv_freq):
    B, T, _ = x.shape
    H = cfg.n_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if "wq" in params:
        q = dense(params["wq"], x)
    else:
        q = dense(params["q_up"],
                  rms_norm(params["q_norm"], dense(params["q_down"], x), cfg.norm_eps))
    q = q.reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, inv_freq)

    c = dense(params["kv_down"], x)
    ckv = rms_norm(params["kv_norm"], c[..., : cfg.kv_lora_rank], cfg.norm_eps)
    krope = c[..., cfg.kv_lora_rank :].reshape(B, T, 1, rope)
    krope = apply_rope(krope, positions, inv_freq)[:, :, 0, :]
    return q_nope, q_rope, ckv, krope


def mla_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale: 1/sqrt(nope + rope), times YaRN's mscale(factor,
    mscale_all_dim) squared where the rope is YaRN-scaled (HF
    ``DeepseekV2Attention.softmax_scale``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_apply(params, cfg: ModelConfig, x, positions, inv_freq, *, cache, cache_index):
    B, T, _ = x.shape
    H = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = mla_scale(cfg)
    q_nope, q_rope, ckv, krope = _mla_qkv_rope(params, cfg, x, positions, inv_freq)

    w_up_full = params["kv_up"]["w"].reshape(cfg.kv_lora_rank, H, nope + vh)

    def _latent_expand(ckv_b, krope_b):
        """Per-block latent -> per-head K/V (never materializes full K)."""
        kv_b = jnp.einsum("bsl,lhx->bshx", ckv_b, w_up_full.astype(x.dtype))
        k_b = jnp.concatenate(
            [kv_b[..., :nope],
             jnp.broadcast_to(krope_b[:, :, None, :], krope_b.shape[:2] + (H, rope))],
            axis=-1,
        )
        return k_b, kv_b[..., nope:]

    if cache is None:
        q = jnp.concatenate([q_nope, q_rope], -1)
        if T * T > FLASH_THRESHOLD:
            from repro.models.flash_xla import flash_sdpa

            out = flash_sdpa(q, (ckv, krope), positions, jnp.arange(T, dtype=jnp.int32),
                             scale=scale, kv_expand=_latent_expand)
        else:
            # direct path: expand per-head K/V from the latent
            kv = dense(params["kv_up"], ckv).reshape(B, T, H, nope + vh)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, T, H, rope))], -1
            )
            bias = _mask_bias(positions, jnp.arange(T), None)
            out = _sdpa(q, k, v, bias, scale, None)
        new_cache = None
    else:
        # absorbed path: attend in the latent space (kv_lora_rank-dim)
        cache = {
            "ckv": cache_write(cache["ckv"], ckv, cache_index),
            "krope": cache_write(cache["krope"], krope, cache_index),
        }
        S = cache["ckv"].shape[1]
        if T > 1 and T * S > FLASH_THRESHOLD:
            from repro.models.flash_xla import flash_sdpa

            q = jnp.concatenate([q_nope, q_rope], -1)
            out = flash_sdpa(
                q, (cache["ckv"].astype(x.dtype), cache["krope"].astype(x.dtype)),
                positions, jnp.arange(S, dtype=jnp.int32),
                scale=scale, kv_expand=_latent_expand,
            )
            y = dense(params["wo"], out.reshape(B, T, H * vh))
            return y, cache
        # kv_up columns are head-interleaved: [h0: nope+vh | h1: nope+vh | ...]
        w_up = params["kv_up"]["w"].reshape(cfg.kv_lora_rank, H, nope + vh)
        w_uk = w_up[..., :nope]
        w_uv = w_up[..., nope:]
        q_eff = jnp.einsum("bthn,lhn->bthl", q_nope, w_uk.astype(x.dtype))  # (B,T,H,L)
        c = cache["ckv"].astype(x.dtype)  # (B,S,L)
        kr = cache["krope"].astype(x.dtype)  # (B,S,rope)
        scores = jnp.einsum("bthl,bsl->bhts", q_eff, c)
        scores = scores + jnp.einsum("bthr,bsr->bhts", q_rope, kr)
        scores = scores.astype(jnp.float32) * scale
        # (B,1,T,S) broadcasts over heads of (B,H,T,S)
        scores = scores + _mask_bias(positions, jnp.arange(S), None).astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhts,bsl->bthl", probs, c)
        out = jnp.einsum("bthl,lhv->bthv", o_lat, w_uv.astype(x.dtype))
        new_cache = cache
    y = dense(params["wo"], out.reshape(B, T, H * vh))
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder): K/V precomputed from encoder output
# ---------------------------------------------------------------------------


def cross_attn_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype=dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype=dtype),
    }


def cross_kv(params, cfg: ModelConfig, enc_out):
    B, S, _ = enc_out.shape
    k = dense(params["wk"], enc_out).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = dense(params["wv"], enc_out).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}


def cross_attn_apply(params, cfg: ModelConfig, x, kv):
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = dense(params["wq"], x).reshape(B, T, cfg.n_heads, hd)
    q_pos = jnp.zeros((B, T), jnp.int32)  # non-causal: positions unused
    out = _attend(
        q, kv["k"].astype(x.dtype), kv["v"].astype(x.dtype),
        q_pos, kv["k"].shape[1], None, 1.0 / hd**0.5, None, causal=False,
    )
    return dense(params["wo"], out.reshape(B, T, cfg.n_heads * hd))
