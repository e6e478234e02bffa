"""Shared primitive layers: norms, RoPE, gated FFNs, softcap, inits.

All modules are functional: ``init_*`` returns a params pytree (plain dicts),
``*_apply``-style functions take ``(params, x, ...)``. Compute dtype follows
the input; weight matrices are drawn in the ``dtype`` the caller asks for
(fp32 by default, matching mixed-precision training practice; bf16 for
serving) and cast at use. Norm scales and biases stay fp32.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def truncated_normal(rng, shape, std=0.02, dtype=jnp.float32):
    return std * jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype)


def dense_init(rng, d_in, d_out, *, std=None, bias=False, dtype=jnp.float32):
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal(rng, (d_in, d_out), std=std, dtype=dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(p, x):
    w = p["w"].astype(x.dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p, x, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(dt)


def layer_norm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(p, x, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Softcap / activations
# ---------------------------------------------------------------------------


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


# ---------------------------------------------------------------------------
# RoPE (full / partial "2d")
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, rotary_pct: float, theta: float):
    """Inverse frequencies for the rotated prefix of the head dim."""
    rot_dim = int(head_dim * rotary_pct)
    rot_dim -= rot_dim % 2
    if rot_dim == 0:
        return None
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    return inv  # (rot_dim//2,)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context extended ``factor`` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(beta_fast: float, beta_slow: float, dim: int,
                          theta: float, max_pos: int):
    """(low, high) rotation pairs: pairs at or below ``low`` turn more than
    ``beta_fast`` times over the original context and keep their frequency;
    pairs at or above ``high`` turn fewer than ``beta_slow`` times and are
    interpolated (HF ``yarn_find_correction_range``)."""
    def pair(turns):
        return dim * math.log(max_pos / (turns * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(pair(beta_fast)), 0), min(math.ceil(pair(beta_slow)), dim - 1)


def yarn_freqs(dim: int, theta: float, rs):
    """Inverse frequencies of YaRN rope (HF ``DeepseekV2YarnRotaryEmbedding``)
    for a ``RopeScaling`` ``rs``: plain frequencies up to the low correction
    pair, frequencies divided by ``rs.factor`` from the high one, a linear
    ramp between. HF also scales cos/sin by mscale(factor, mscale) /
    mscale(factor, mscale_all_dim); only configurations where that is 1
    are accepted."""
    if yarn_mscale(rs.factor, rs.mscale) != yarn_mscale(rs.factor, rs.mscale_all_dim):
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos/sin; not supported")
    extra = rope_freqs(dim, 1.0, theta)
    low, high = yarn_correction_range(rs.beta_fast, rs.beta_slow, dim, theta,
                                      rs.original_max_position_embeddings)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return extra / rs.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, inv_freq):
    """x: (..., S, H, head_dim); positions: (..., S) int32.

    Rotates the leading ``2*len(inv_freq)`` dims (half-split convention),
    passes the rest through — implements both full RoPE and ChatGLM-style
    partial ("2d") RoPE.
    """
    if inv_freq is None:
        return x
    rot = 2 * inv_freq.shape[0]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# FFN (SwiGLU / GeGLU / plain)
# ---------------------------------------------------------------------------


def ffn_init(rng, d_model, d_ff, *, glu=True, bias=False, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    p = {"up": dense_init(ks[0], d_model, d_ff, bias=bias, dtype=dtype),
         "down": dense_init(ks[1], d_ff, d_model, bias=bias, dtype=dtype)}
    if glu:
        p["gate"] = dense_init(ks[2], d_model, d_ff, bias=bias, dtype=dtype)
    return p


def ffn(p, x, act_name="silu", glu=True):
    a = act_fn(act_name)
    up = dense(p["up"], x)
    h = a(dense(p["gate"], x)) * up if glu else a(up)
    return dense(p["down"], h)
