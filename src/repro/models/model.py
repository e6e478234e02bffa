"""Unified model interface over every assigned architecture.

``build_model(cfg)`` returns a ``Model`` with:
  init(rng)                                    -> params
  forward(params, batch)                       -> (logits, moe_aux)   # full-seq causal
  loss(params, batch)                          -> (scalar, metrics)
  init_cache(batch, max_len, dtype)            -> cache pytree
  cache_specs(batch, max_len, dtype)           -> ShapeDtypeStruct pytree (no alloc)
  prefill(params, batch, cache, index)         -> (last_logits, cache)
  decode_step(params, tokens, cache, index)    -> (logits, cache)

``batch`` is a dict: {"tokens": (B,S) int32[, "labels": (B,S)][, "frontend_embeds":
(B,F,d)][, "frames": (B,F,d)]}. ``index`` may be a scalar (uniform offsets) or a
(B,) vector (continuous batching).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, stack
from repro.models.layers import (
    rms_norm,
    rms_norm_init,
    rope_freqs,
    softcap,
    truncated_normal,
    yarn_freqs,
)


def _positions_from_index(index, B, T):
    index = jnp.asarray(index)
    if index.ndim == 0:
        return index + jnp.arange(T, dtype=jnp.int32)[None, :] + jnp.zeros((B, 1), jnp.int32)
    return index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]


def cross_entropy(logits, targets, mask=None):
    """fp32 CE; logits (B,T,V), targets (B,T).

    Sharding: batch over DP and sequence over the model axis — keeps the
    fp32 logits (the single biggest training tensor) fully distributed.
    """
    from repro.distributed.ctx import shard_act

    logits = shard_act(logits.astype(jnp.float32), "dp", "model", None)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    dtype: Any = jnp.float32  # activations
    remat: bool = False
    param_dtype: Any = jnp.float32  # weight matrices drawn by init()

    def __post_init__(self):
        cfg = self.cfg
        if cfg.mla and cfg.rope_scaling is not None:
            self.inv_freq = yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta,
                                       cfg.rope_scaling)
        elif cfg.mla:
            self.inv_freq = rope_freqs(cfg.qk_rope_head_dim, 1.0, cfg.rope_theta)
        elif cfg.n_heads:
            self.inv_freq = rope_freqs(cfg.head_dim, cfg.rotary_pct, cfg.rope_theta)
        else:
            self.inv_freq = None

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Weight matrices are drawn directly in ``param_dtype`` (no fp32
        draw then cast); norm scales and biases stay fp32."""
        cfg = self.cfg
        dt = self.param_dtype
        if cfg.encdec:
            return encdec.encdec_init(rng, cfg, dt)
        k_embed, k_stack, k_head, k_pos = jax.random.split(rng, 4)
        params = {
            "embed": truncated_normal(k_embed, (cfg.vocab_size, cfg.d_model), std=0.02,
                                      dtype=dt),
            "stack": stack.stack_init(k_stack, cfg, dt),
            "final_norm": rms_norm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = truncated_normal(
                k_head, (cfg.d_model, cfg.vocab_size), std=0.02, dtype=dt
            )
        if cfg.learned_pos:
            params["pos"] = truncated_normal(k_pos, (cfg.max_seq_len, cfg.d_model), std=0.01,
                                             dtype=dt)
        return params

    # ------------------------------------------------------------- embeddings
    def _embed(self, params, tokens, frontend_embeds=None, positions=None):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model**0.5, self.dtype)
        if frontend_embeds is not None:
            x = jnp.concatenate([frontend_embeds.astype(self.dtype), x], axis=1)
        if cfg.learned_pos and positions is not None:
            x = x + jnp.take(
                params["pos"], jnp.clip(positions, 0, cfg.max_seq_len - 1), axis=0
            ).astype(self.dtype)
        return x

    def _head(self, params, h):
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = h @ w.astype(h.dtype)
        return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)

    # ---------------------------------------------------------------- forward
    def forward(self, params, batch):
        cfg = self.cfg
        if cfg.encdec:
            return self._encdec_forward(params, batch)
        tokens = batch["tokens"]
        fe = batch.get("frontend_embeds")
        B, S_text = tokens.shape
        F = fe.shape[1] if fe is not None else 0
        positions = jnp.broadcast_to(jnp.arange(F + S_text, dtype=jnp.int32), (B, F + S_text))
        x = self._embed(params, tokens, fe, positions)
        h, _, aux = stack.stack_apply(
            params["stack"], cfg, x, positions, self.inv_freq, remat=self.remat
        )
        h = rms_norm(params["final_norm"], h, cfg.norm_eps)
        if F:
            h = h[:, F:]
        return self._head(params, h), aux

    def _encdec_forward(self, params, batch):
        cfg = self.cfg
        frames, tokens = batch["frames"], batch["tokens"]
        B, T = tokens.shape
        enc_out = encdec.encode(params, cfg, frames.astype(self.dtype), remat=self.remat)
        cross = encdec.cross_kv_all(params, cfg, enc_out)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        h, _ = encdec.decode_trunk(
            params, cfg, tokens, positions, cache={"self": None, "cross": cross},
            remat=self.remat,
        )
        w = params["embed"].T
        return (h @ w.astype(h.dtype)).astype(jnp.float32), jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------- loss
    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        labels = batch.get("labels")
        if labels is None:
            # next-token via roll + mask (not slicing): keeps the seq dim a
            # multiple of the model axis so the fp32 logits stay sharded
            labels = jnp.roll(tokens, -1, axis=1)
            mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
        else:
            mask = None
        ce = cross_entropy(logits, labels, mask)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "moe_aux": aux}

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        if cfg.encdec:
            return encdec.dec_cache_init(cfg, batch, max_len, dtype)
        return stack.stack_cache_init(cfg, batch, max_len, dtype)

    def cache_specs(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len, dtype))

    # ---------------------------------------------------------------- serving
    def prefill(self, params, batch, cache, index):
        """Run a (chunked) prefill segment; returns (last-position logits, cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        if cfg.encdec:
            enc_out = encdec.encode(params, cfg, batch["frames"].astype(self.dtype))
            cross = encdec.cross_kv_all(params, cfg, enc_out)
            # materialized cross K/V becomes part of the cache
            cache = {"self": cache["self"], "cross": jax.tree.map(
                lambda dst, src: src.astype(dst.dtype), cache["cross"], cross)}
            positions = _positions_from_index(index, B, T)
            h, cache = encdec.decode_trunk(
                params, cfg, tokens, positions, cache=cache, cache_index=index
            )
            logits = (h[:, -1] @ params["embed"].T.astype(h.dtype)).astype(jnp.float32)
            return logits, cache
        fe = batch.get("frontend_embeds")
        F = fe.shape[1] if fe is not None else 0
        positions = _positions_from_index(index, B, F + T)
        x = self._embed(params, tokens, fe, positions)
        h, cache, _ = stack.stack_apply(
            params["stack"], cfg, x, positions, self.inv_freq,
            caches=cache, cache_index=index,
        )
        h = rms_norm(params["final_norm"], h[:, -1:], cfg.norm_eps)
        return self._head(params, h)[:, 0], cache

    def decode_step(self, params, tokens, cache, index):
        """tokens: (B, 1) -> (logits (B, V), new cache)."""
        cfg = self.cfg
        B, T = tokens.shape
        positions = _positions_from_index(index, B, T)
        if cfg.encdec:
            h, cache = encdec.decode_trunk(
                params, cfg, tokens, positions, cache=cache, cache_index=index
            )
            logits = (h[:, -1] @ params["embed"].T.astype(h.dtype)).astype(jnp.float32)
            return logits, cache
        x = self._embed(params, tokens, None, positions)
        h, cache, _ = stack.stack_apply(
            params["stack"], cfg, x, positions, self.inv_freq,
            caches=cache, cache_index=index,
        )
        h = rms_norm(params["final_norm"], h[:, -1:], cfg.norm_eps)
        return self._head(params, h)[:, 0], cache


def build_model(cfg: ModelConfig, dtype=jnp.float32, remat: bool = False,
                param_dtype=jnp.float32) -> Model:
    return Model(cfg=cfg, dtype=dtype, remat=remat, param_dtype=param_dtype)
