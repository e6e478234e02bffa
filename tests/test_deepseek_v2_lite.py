"""DeepSeek-V2-Lite: its published widths, YaRN at the published values,
the direct query projection wherever MLA is sized, and the serving MoE path
(routed rows only, never dropped).

Numbers the tests check are written out from the published configuration
and HF ``modeling_deepseek.py``'s formulas, not read back from the code."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.configs.reduced import cut_depth, dropless
from repro.models import build_model, moe
from repro.models.attention import mla_scale
from repro.models.layers import ffn, yarn_correction_range, yarn_freqs


def test_published_widths_and_the_cut():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.tie_embeddings) == (
        27, 2048, 102400, False)
    assert (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (16, 0, 512, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff, cfg.shared_d_ff, cfg.d_ff) == (
        64, 6, 1408, 2816, 10944)
    assert not cfg.norm_topk and cfg.rope_theta == 10000.0
    assert [s.ffn for s in cfg.layer_specs[:2]] == ["dense", "moe"]
    # 15.7B parameters, 2.4B active besides the embedding (the model card)
    assert 15.5e9 < cfg.param_count() < 15.9e9
    assert 2.35e9 < cfg.active_param_count() - 102400 * 2048 < 2.5e9
    cut = cut_depth(cfg, 9)  # one stage of three: the dense layer + 8 MoE
    assert cut.n_prefix_layers == 1 and cut.n_periods == 8
    assert [s.ffn for s in cut.layer_specs] == ["dense"] + ["moe"] * 8
    # 5.18B parameters in the cut, 10.36 GB in bf16
    assert 5.15e9 < cut.param_count() < 5.21e9


def test_yarn_at_the_published_values():
    cfg = get_config("deepseek-v2-lite")
    # yarn_find_correction_range(32, 1, 64, 10000, 4096)
    assert yarn_correction_range(32.0, 1.0, 64, 10000.0, 4096) == (10, 23)
    # mscale = 0.1 * 0.707 * ln 40 + 1 = 1.26080; the softmax scale gains its square
    assert mla_scale(cfg) * math.sqrt(192) == pytest.approx(1.58963, abs=2e-5)
    inv = np.asarray(yarn_freqs(64, 10000.0, cfg.rope_scaling))
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)  # pairs 0..10 kept
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)  # 23.. over 40
    mid = inv[11:23] / plain[11:23]
    assert np.all((mid < 1) & (mid > 1 / 40)) and np.all(np.diff(mid) < 0)
    # the served model uses these frequencies
    model = build_model(cut_depth(cfg, 9))
    np.testing.assert_array_equal(np.asarray(model.inv_freq), inv)


def test_query_lora_is_kept_where_published():
    lite = reduce_config(get_config("deepseek-v2-lite"))
    assert lite.q_lora_rank == 0 and lite.rope_scaling is not None
    p = jax.eval_shape(build_model(lite).init, jax.random.PRNGKey(0))
    mixer = p["stack"]["prefix"][0]["mixer"]
    assert set(mixer) == {"wq", "kv_down", "kv_norm", "kv_up", "wo"}
    assert mixer["wq"]["w"].shape == (64, 4 * (16 + 8))
    assert reduce_config(get_config("deepseek-v2-236b")).q_lora_rank == 32


def test_direct_query_is_counted_and_v2_236b_is_unchanged():
    from repro.sim.opcost import _attn_qkvo_matmuls, _attn_weight_bytes

    lite = get_config("deepseek-v2-lite")
    d, H = 2048, 16
    mixer = d * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    assert lite._mixer_params(lite.layer_specs[0]) == mixer
    assert _attn_weight_bytes(lite) == 2 * mixer
    assert _attn_qkvo_matmuls(lite, 5)[0] == (5, d, H * 192)
    assert len(_attn_qkvo_matmuls(lite, 5)) == 4
    # DeepSeek-V2 (236B) keeps its query LoRA: d x 1536, norm, 1536 x H*192
    big = get_config("deepseek-v2-236b")
    d, H = 5120, 128
    mixer = d * 1536 + 1536 * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    assert big._mixer_params(big.layer_specs[0]) == mixer
    assert _attn_weight_bytes(big) == 2 * mixer
    assert _attn_qkvo_matmuls(big, 5)[:2] == [(5, d, 1536), (5, 1536, H * 192)]
    assert big.param_count() == 235_741_312_000  # as before direct queries


def _loop(p, cfg, x, valid):
    """Each real row through its top-k experts one expert at a time."""
    probs = jax.nn.softmax(x @ p["router"]["w"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    out = np.zeros(x.shape, np.float64)
    for n in np.nonzero(np.asarray(valid))[0]:
        for k in range(cfg.top_k):
            e = int(top_i[n, k])
            pe = jax.tree.map(lambda a: a[e], p["experts"])
            out[n] += float(top_w[n, k]) * np.asarray(ffn(pe, x[n][None], cfg.act, cfg.glu))[0]
    return out


@pytest.mark.parametrize("interpret", [False, True])  # ragged_dot / the kernel
@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "qwen3-moe-30b-a3b"])
def test_routed_moe_equals_a_per_expert_loop_and_drops_nothing(arch, interpret):
    cfg = reduce_config(get_config(arch))
    assert cfg.moe_capacity_factor == 1.25  # the training capacity, as served
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    # rows that share a direction send most of their routing to few experts,
    # past what a capacity of 1.25 x the mean load holds
    base = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.d_model))
    x = base + 0.05 * jax.random.normal(jax.random.PRNGKey(2), (40, cfg.d_model))
    valid = jnp.arange(40) < 37
    y, rows = moe.moe_routed(p, cfg, x, valid, interpret=interpret)
    assert int(rows.sum()) == 37 * cfg.top_k and int(rows.max()) > math.ceil(
        1.25 * 37 * cfg.top_k / cfg.n_experts)
    np.testing.assert_allclose(np.asarray(y)[:37], _loop(p, cfg, x, valid)[:37],
                               rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(y)[37:] == 0)  # padding rows join no group
    # the training path at the same capacity drops rows; dropless it agrees
    y_cap, _ = moe._moe_apply_local(p, cfg, x[None])
    y_all, _ = moe._moe_apply_local(p, dropless(cfg), x[None])
    shared = ffn(p["shared"], x, cfg.act, cfg.glu) if cfg.n_shared_experts else 0.0
    np.testing.assert_allclose(np.asarray(y_all[0] - shared)[:37], np.asarray(y)[:37],
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(y_cap[0] - shared)[:37], np.asarray(y)[:37])


@pytest.mark.parametrize("kernel", [False, True])
def test_routed_moe_is_sized_by_the_routed_rows(kernel):
    """The expert matmuls take the 136 x 6 routed rows, each expert's group
    padded at most to the 16-row tile (816 + 64 x 15 = 1776 rows), not
    n_experts x 136: the Pallas grouped matmul on TPU, ragged_dot on CPU."""
    cfg = dataclasses.replace(reduce_config(get_config("deepseek-v2-lite")),
                              n_experts=64, top_k=6)
    p = jax.eval_shape(lambda: moe.moe_init(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((136, cfg.d_model), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe.moe_routed(p, cfg, x, use_kernel=kernel))(p, x)

    def eqns(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)
    found = [e for e in eqns(jaxpr.jaxpr)
             if e.primitive.name in ("ragged_dot_general", "pallas_call")]
    assert len(found) == (2 if kernel else 3)  # (gate, up) and down
    for e in found:
        x_in = [v.aval.shape for v in e.invars if len(v.aval.shape) == 2]
        assert (1776, cfg.d_model) in x_in or (1776, cfg.moe_d_ff) in x_in, x_in
        assert e.outvars[0].aval.shape[0] == 1776


def test_engine_sums_expert_counts_on_the_device():
    """A MoE model's paged engine keeps the routed-row counts on the device
    across steps (no per-step fetch) and hands back the totals on request:
    every real row goes to top_k experts in each MoE layer; a dense model
    has no counts."""
    from repro.core.scheduler import SchedulerConfig
    from repro.serving.engine import Engine
    from repro.serving.request import Request

    def engine(cfg):
        model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
        return Engine(model, model.init(jax.random.PRNGKey(0)),
                      SchedulerConfig(chunk_size=16, max_decode_batch=2, kv_block_size=16,
                                      prefetch_buffer_bytes=1 << 20),
                      max_len=64, cache_dtype=jnp.float32)

    cfg = reduce_config(get_config("deepseek-v2-lite"))
    eng = engine(cfg)
    assert eng.attn_kernel == "paged"
    rows0, hit0 = eng.expert_counts()
    n_moe = sum(1 for sp in cfg.layer_specs if sp.ffn == "moe")
    assert rows0.shape == (n_moe, cfg.n_experts) and rows0.sum() == 0 and hit0 == 0
    rng = np.random.default_rng(0)
    lens, new = (21, 9), 3
    for rid, n in enumerate(lens):
        eng.submit(Request(rid=rid, prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                           max_new_tokens=new))
    eng.run(max_steps=50)
    assert all(len(eng.scheduler.requests[r].output) == new for r in range(len(lens)))
    rows, hit = eng.expert_counts()
    real = sum(n + new - 1 for n in lens)  # prompt rows, then one row per later token
    assert rows.sum() == real * cfg.top_k * n_moe
    assert n_moe * cfg.top_k * eng.steps_run <= hit <= n_moe * cfg.n_experts * eng.steps_run
    assert engine(reduce_config(get_config("llama3.1-8b"))).expert_counts() is None
