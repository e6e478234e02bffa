"""Whisper-style encoder-decoder backbone (conv audio frontend stubbed).

Encoder: bidirectional self-attention over precomputed frame embeddings (the
conv1d×2 + GELU frontend is a stub per the assignment — ``input_specs()``
feeds (B, frontend_len, d_model) frame embeddings directly) + sinusoidal
positions. Decoder: causal self-attention (cached) + cross-attention over the
encoder output (K/V computed once at encode time and cached — the natural
prefetch target noted in DESIGN.md §4). LayerNorm everywhere (not RMS).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention
from repro.models.layers import dense, ffn, ffn_init, layer_norm, layer_norm_init, truncated_normal


def sinusoids(length: int, channels: int):
    log_timescale = np.log(10000) / (channels // 2 - 1)
    inv = np.exp(-log_timescale * np.arange(channels // 2))
    ang = np.arange(length)[:, None] * inv[None, :]
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], axis=1), jnp.float32)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _enc_layer_init(rng, cfg: ModelConfig, dtype):
    ks = jax.random.split(rng, 2)
    return {
        "norm1": layer_norm_init(cfg.d_model),
        "attn": attention.attn_init(ks[0], cfg, dtype),
        "norm2": layer_norm_init(cfg.d_model),
        "ffn": ffn_init(ks[1], cfg.d_model, cfg.d_ff, glu=cfg.glu, bias=True, dtype=dtype),
    }


def _dec_layer_init(rng, cfg: ModelConfig, dtype):
    ks = jax.random.split(rng, 3)
    return {
        "norm1": layer_norm_init(cfg.d_model),
        "self_attn": attention.attn_init(ks[0], cfg, dtype),
        "norm_x": layer_norm_init(cfg.d_model),
        "cross_attn": attention.cross_attn_init(ks[1], cfg, dtype),
        "norm2": layer_norm_init(cfg.d_model),
        "ffn": ffn_init(ks[2], cfg.d_model, cfg.d_ff, glu=cfg.glu, bias=True, dtype=dtype),
    }


def encdec_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, cfg.n_enc_layers + cfg.n_layers + 2)
    enc_layers = [_enc_layer_init(ks[i], cfg, dtype) for i in range(cfg.n_enc_layers)]
    dec_layers = [
        _dec_layer_init(ks[cfg.n_enc_layers + i], cfg, dtype) for i in range(cfg.n_layers)
    ]
    return {
        "enc": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers),
        "dec": jax.tree.map(lambda *xs: jnp.stack(xs), *dec_layers),
        "enc_norm": layer_norm_init(cfg.d_model),
        "dec_norm": layer_norm_init(cfg.d_model),
        "embed": truncated_normal(ks[-2], (cfg.vocab_size, cfg.d_model), std=0.02,
                                  dtype=dtype),
        "pos_dec": truncated_normal(ks[-1], (cfg.max_seq_len, cfg.d_model), std=0.01,
                                    dtype=dtype),
    }


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def encode(params, cfg: ModelConfig, frames, remat: bool = False):
    """frames: (B, F, d_model) stub embeddings -> (B, F, d_model)."""
    B, F, _ = frames.shape
    h = frames + sinusoids(F, cfg.d_model).astype(frames.dtype)

    def body(h, p):
        hn = layer_norm(p["norm1"], h, cfg.norm_eps)
        # bidirectional: no mask bias
        q = dense(p["attn"]["wq"], hn).reshape(B, F, cfg.n_heads, cfg.head_dim)
        k = dense(p["attn"]["wk"], hn).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
        v = dense(p["attn"]["wv"], hn).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
        bias = jnp.zeros((B, 1, F, F), h.dtype)
        o = attention._sdpa(q, k, v, bias, 1.0 / cfg.head_dim**0.5, None)
        h = h + dense(p["attn"]["wo"], o.reshape(B, F, -1))
        hn = layer_norm(p["norm2"], h, cfg.norm_eps)
        h = h + ffn(p["ffn"], hn, cfg.act, cfg.glu)
        return h, None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["enc"])
    return layer_norm(params["enc_norm"], h, cfg.norm_eps)


def cross_kv_all(params, cfg: ModelConfig, enc_out):
    """Cross-attention K/V for every decoder layer: leaves (L, B, F, H, hd)."""
    return jax.vmap(
        lambda p: attention.cross_kv(p["cross_attn"], cfg, enc_out)
    )(params["dec"])


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------


def dec_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    self_kv = attention.kv_cache_init(cfg, batch, max_len, dtype)
    cross = {
        "k": jnp.zeros((batch, cfg.frontend_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cfg.frontend_len, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape).copy(), t
    )
    return {"self": stack(self_kv), "cross": stack(cross)}


def decode_trunk(params, cfg: ModelConfig, tokens, positions, *, cache=None,
                 cache_index=None, remat: bool = False):
    """Decoder stack. cache=None -> full causal (training; cross K/V from cache arg is
    then required via params-side precompute; instead training passes enc_out through
    ``cache={"cross": ...}`` with self=None)."""
    B, T = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x + jnp.take(
        params["pos_dec"], jnp.clip(positions, 0, cfg.max_seq_len - 1), axis=0
    ).astype(x.dtype)

    spec = cfg.layer_specs[0]
    self_caches = cache["self"] if cache is not None and cache.get("self") is not None else None
    cross = cache["cross"]

    def body(h, xs):
        p, ckv, skv = xs
        hn = layer_norm(p["norm1"], h, cfg.norm_eps)
        y, new_skv = attention.attn_apply(
            p["self_attn"], cfg, spec, hn, positions, None, cache=skv, cache_index=cache_index
        )
        h = h + y
        hn = layer_norm(p["norm_x"], h, cfg.norm_eps)
        h = h + attention.cross_attn_apply(p["cross_attn"], cfg, hn, ckv)
        hn = layer_norm(p["norm2"], h, cfg.norm_eps)
        h = h + ffn(p["ffn"], hn, cfg.act, cfg.glu)
        return h, new_skv

    if self_caches is not None:
        def sbody(h, xs):
            h, new_skv = body(h, xs)
            return h, new_skv

        h, new_self = jax.lax.scan(sbody, x, (params["dec"], cross, self_caches))
        new_cache = {"self": new_self, "cross": cross}
    else:
        def nbody(h, xs):
            p, ckv = xs
            h, _ = body(h, (p, ckv, None))
            return h, None

        if remat:
            nbody = jax.checkpoint(nbody)
        h, _ = jax.lax.scan(nbody, x, (params["dec"], cross))
        new_cache = None

    h = layer_norm(params["dec_norm"], h, cfg.norm_eps)
    return h, new_cache
