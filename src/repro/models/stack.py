"""Period-scan decoder stack.

Layers are grouped into repeating *periods* (jamba: 8, gemma2: 2, most: 1);
parameters for each period position are stacked over periods and the stack is
traversed with ``lax.scan``. This keeps HLO size O(period) instead of
O(n_layers) — essential for compiling 60–80-layer configs across 68 dry-run
cells — and gives a natural remat boundary (one period).

Caches (KV / SSM state) follow the same layout: a dict keyed by period
position, each leaf stacked over periods, consumed/produced as scan xs/ys.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.ctx import shard_act
from repro.models import attention, mamba, moe
from repro.models.layers import ffn, ffn_init, rms_norm, rms_norm_init


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------


def layer_init(rng, cfg: ModelConfig, spec: LayerSpec, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {"norm1": rms_norm_init(cfg.d_model)}
    if spec.mixer == "attn":
        p["mixer"] = attention.attn_init(ks[0], cfg, dtype)
    elif spec.mixer == "mamba2":
        p["mixer"] = mamba.mamba2_init(ks[0], cfg, dtype)
    elif spec.mixer == "mamba1":
        p["mixer"] = mamba.mamba1_init(ks[0], cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if cfg.post_norm:
        p["post_norm1"] = rms_norm_init(cfg.d_model)
    if spec.ffn != "none":
        p["norm2"] = rms_norm_init(cfg.d_model)
        if spec.ffn == "dense":
            p["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff, glu=cfg.glu, dtype=dtype)
        else:
            p["ffn"] = moe.moe_init(ks[1], cfg, dtype)
        if cfg.post_norm:
            p["post_norm2"] = rms_norm_init(cfg.d_model)
    return p


def layer_cache_init(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int, dtype):
    if spec.mixer == "attn":
        return attention.kv_cache_init(cfg, batch, max_len, dtype)
    if spec.mixer == "mamba2":
        return mamba.mamba2_state_init(cfg, batch)
    if spec.mixer == "mamba1":
        return mamba.mamba1_state_init(cfg, batch)
    raise ValueError(spec.mixer)


def layer_apply(
    p,
    cfg: ModelConfig,
    spec: LayerSpec,
    h,
    positions,
    inv_freq,
    *,
    cache=None,
    cache_index=None,
):
    """Returns (h, new_cache, moe_aux)."""
    hn = rms_norm(p["norm1"], h, cfg.norm_eps)
    if spec.mixer == "attn":
        y, new_cache = attention.attn_apply(
            p["mixer"], cfg, spec, hn, positions, inv_freq, cache=cache, cache_index=cache_index
        )
    elif spec.mixer == "mamba2":
        y, new_cache = mamba.mamba2_apply(p["mixer"], cfg, hn, state=cache)
    else:
        y, new_cache = mamba.mamba1_apply(p["mixer"], cfg, hn, state=cache)
    if cfg.post_norm:
        y = rms_norm(p["post_norm1"], y, cfg.norm_eps)
    h = h + y

    aux = jnp.zeros((), jnp.float32)
    if spec.ffn != "none":
        hn = rms_norm(p["norm2"], h, cfg.norm_eps)
        if spec.ffn == "dense":
            y = ffn(p["ffn"], hn, cfg.act, cfg.glu)
        else:
            y, aux = moe.moe_apply(p["ffn"], cfg, hn)
        if cfg.post_norm:
            y = rms_norm(p["post_norm2"], y, cfg.norm_eps)
        h = h + y
    # Megatron-SP-style residual sharding: batch over DP, sequence over the
    # model axis between blocks (no-op unless a mesh is installed + divisible)
    h = shard_act(h, "dp", "model", None)
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    n_pre = cfg.n_prefix_layers
    rngs = jax.random.split(rng, cfg.n_layers)
    prefix = [layer_init(rngs[i], cfg, cfg.layer_specs[i], dtype) for i in range(n_pre)]

    def period_init(keys):
        return {
            str(i): layer_init(keys[i], cfg, cfg.period_specs[i], dtype)
            for i in range(cfg.scan_period)
        }

    # vmapped over periods: the draws equal per-layer draws bit for bit, the
    # traced program holds one period (not n_layers), and the stacked
    # params are built in place rather than stacked from per-layer copies
    keys = rngs[n_pre:].reshape((cfg.n_periods, cfg.scan_period) + rngs.shape[1:])
    stacked = jax.vmap(period_init)(keys) if cfg.n_periods else {}
    return {"prefix": prefix, "periods": stacked}


def stack_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    prefix = [
        layer_cache_init(cfg, cfg.layer_specs[i], batch, max_len, dtype)
        for i in range(cfg.n_prefix_layers)
    ]
    one_period = {
        str(i): layer_cache_init(cfg, cfg.period_specs[i], batch, max_len, dtype)
        for i in range(cfg.scan_period)
    }
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape).copy(), one_period
    )
    return {"prefix": prefix, "periods": stacked}


def stack_apply(
    params,
    cfg: ModelConfig,
    h,
    positions,
    inv_freq,
    *,
    caches=None,
    cache_index=None,
    remat: bool = False,
):
    """Returns (h, new_caches|None, moe_aux_total)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix = []
    for i in range(cfg.n_prefix_layers):
        c = caches["prefix"][i] if caches is not None else None
        h, nc, aux = layer_apply(
            params["prefix"][i], cfg, cfg.layer_specs[i], h, positions, inv_freq,
            cache=c, cache_index=cache_index,
        )
        new_prefix.append(nc)
        aux_total = aux_total + aux

    if cfg.n_periods == 0:
        return h, caches, aux_total

    def period_fn(h, p_period, cache_period):
        aux_p = jnp.zeros((), jnp.float32)
        new_cache = {}
        for i in range(cfg.scan_period):
            spec = cfg.period_specs[i]
            c = cache_period[str(i)] if cache_period is not None else None
            h, nc, aux = layer_apply(
                p_period[str(i)], cfg, spec, h, positions, inv_freq,
                cache=c, cache_index=cache_index,
            )
            new_cache[str(i)] = nc
            aux_p = aux_p + aux
        return h, new_cache, aux_p

    if remat:
        period_fn = jax.checkpoint(period_fn)

    if caches is not None:
        def body(carry, xs):
            h, aux = carry
            p_period, cache_period = xs
            h, new_cache, aux_p = period_fn(h, p_period, cache_period)
            return (h, aux + aux_p), new_cache

        (h, aux_total), new_periods = jax.lax.scan(
            body, (h, aux_total), (params["periods"], caches["periods"])
        )
        return h, {"prefix": new_prefix, "periods": new_periods}, aux_total

    def body_nc(carry, p_period):
        h, aux = carry
        h, _, aux_p = period_fn(h, p_period, None)
        return (h, aux + aux_p), None

    (h, aux_total), _ = jax.lax.scan(body_nc, (h, aux_total), params["periods"])
    return h, None, aux_total
