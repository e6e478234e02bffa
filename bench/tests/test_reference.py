"""The plain reference agrees with the program's own float32 forward at
reduced widths, for both configurations' mechanisms (qk-norm with an untied
head; QKV bias with a tied head), and its layer-by-layer weight draw equals
the served tree's."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import dense_gqa as R
from bench.tests.tiny import ROOT

SMALL = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=96,
             vocab_size=300)


def small(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(SMALL)
    return cfg


def program(cfg):
    from repro.configs.base import ModelConfig
    from repro.models import build_model

    mc = ModelConfig(
        name="t", family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=cfg["qk_norm"], qkv_bias=cfg["attention_bias"],
        rope_theta=cfg["rope_theta"], tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"])
    return build_model(mc, dtype=jnp.float32, param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("name", ["qwen3-32b-l8", "qwen2-1.5b"])
def test_reference_matches_program_forward(name):
    cfg = small(name)
    seed = 2**35 + 3
    model = program(cfg)
    params = R.draw_params(cfg, seed)
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert shapes(params) == shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"], 70)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]})
    want = R.logits_at(cfg, seed, toks, np.arange(70))
    scale = float(np.abs(want).max())
    assert float(np.abs(np.asarray(got[0]) - want).max()) < 1e-5 * max(scale, 1.0)


@pytest.mark.parametrize("name", ["qwen3-32b-l8", "qwen2-1.5b"])
def test_layer_draw_equals_served_tree(name):
    cfg = small(name)
    seed = 12345
    params = R.draw_params(cfg, seed)
    draws = R._draws(R._cfg_json(cfg))
    for i in range(cfg["num_hidden_layers"]):
        one = draws["layer"](R.root_key(seed), i)
        stacked = jax.tree.map(lambda a: a[i], params["stack"]["periods"]["0"])
        assert all(jax.tree.leaves(jax.tree.map(
            lambda a, b: bool(jnp.array_equal(a, b)), one, stacked)))


def test_fp8_control_departs_from_the_reference():
    cfg = small("qwen2-1.5b")
    toks = np.random.default_rng(2).integers(0, cfg["vocab_size"], 50)
    ref = R.logits_at(cfg, 7, toks, np.arange(50))
    ctl = R.logits_at(cfg, 7, toks, np.arange(50), fp8=True)
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5
