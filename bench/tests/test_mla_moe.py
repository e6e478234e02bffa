"""The latent-attention + sparse-expert reference (``bench/references/
mla_moe.py``) at reduced widths on the CPU: it agrees with the program's own
float32 forward, the engine's paged serving (prefill, then decode through
the latent page pool; jnp oracle and the latent kernel interpreted) agrees
with it on logits, its layer-by-layer draw equals the served tree, the fp8
control departs from it, and its operation and byte counts check by hand at
the published widths."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import engine_build, flops
from bench.references import mla_moe as R
from bench.tests.tiny import ROOT

FILE = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-l9.json").read_text())
# every mechanism of the file (direct query, YaRN, a dense layer 0, routed
# top-k experts without renormalisation, shared experts) at CPU widths
SMALL = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
             vocab_size=300)


def small():
    return dict(FILE, **SMALL)


def program(cfg, dtype=jnp.float32):
    """The registered architecture at the file's widths and depth."""
    from repro.configs import get_config
    from repro.configs.base import LayerSpec
    from repro.models import build_model

    L = cfg["num_hidden_layers"]
    mc = dataclasses.replace(
        get_config(cfg["arch"]), name="t", n_layers=L, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        moe_capacity_factor=float(cfg["n_routed_experts"]),  # dropless full forward
        layer_specs=tuple(LayerSpec(ffn="moe" if R.is_moe(cfg, i) else "dense")
                          for i in range(L)))
    engine_build.check_widths(cfg, mc)
    return build_model(mc, dtype=dtype, param_dtype=jnp.bfloat16)


def test_reference_matches_program_forward():
    cfg = small()
    seed = 2**35 + 11
    model = program(cfg)
    params = R.draw_params(cfg, seed)
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert shapes(params) == shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"], 70)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]})
    want = R.logits_at(cfg, seed, toks, np.arange(70))
    scale = float(np.abs(want).max())
    # float32 both ways; the program attends in the latent space (absorbed
    # only when cached), sums experts in another order: rounding only
    assert float(np.abs(np.asarray(got[0]) - want).max()) < 1e-5 * max(scale, 1.0)


def test_layer_draw_equals_served_tree():
    cfg = small()
    seed = 12345
    params = R.draw_params(cfg, seed)
    draws = R._draws(R._cfg_json(cfg))
    same = lambda a, b: all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    assert same(draws["layer"](R.root_key(seed), 0, False), params["stack"]["prefix"][0])
    for i in range(1, cfg["num_hidden_layers"]):
        stacked = jax.tree.map(lambda a: a[i - 1], params["stack"]["periods"]["0"])
        assert same(draws["layer"](R.root_key(seed), i, True), stacked)


def test_fp8_control_departs_from_the_reference():
    cfg = small()
    toks = np.random.default_rng(2).integers(0, cfg["vocab_size"], 50)
    ref = R.logits_at(cfg, 7, toks, np.arange(50))
    ctl = R.logits_at(cfg, 7, toks, np.arange(50), fp8=True)
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5


@pytest.mark.parametrize("kernel", ["oracle", "interpret"])
def test_engine_serving_matches_reference_logits(kernel, monkeypatch):
    """Prefill in chunks beside decodes, then decode through the paged
    latent pool, two requests sharing the steps: every sampled row's logits
    against the reference's full forward at that position."""
    from repro.core.scheduler import SchedulerConfig
    from repro.kernels import paged_attention
    from repro.serving.engine import Engine
    from repro.serving.request import Request

    if kernel == "interpret":  # the latent kernel in Pallas interpret mode
        real = paged_attention.ragged_mixed_mla_attention
        monkeypatch.setattr(paged_attention, "ragged_mixed_mla_attention",
                            lambda *a, **k: real(*a, **dict(k, interpret=True)))
    cfg = small()
    seed = 2**33 + 17
    model = program(cfg)
    eng = Engine(model, R.draw_params(cfg, seed),
                 SchedulerConfig(chunk_size=16, max_decode_batch=2, kv_block_size=16,
                                 prefetch_buffer_bytes=1 << 20),
                 max_len=96, cache_dtype=jnp.float32)
    assert eng.attn_kernel == "paged" and eng.expert_counts() is not None
    seen = []  # (rid, logits row) in sampling order
    sample, append = eng._sample_rows, eng._append
    eng._sample_rows = lambda rows: seen.append([np.asarray(rows), 0]) or sample(rows)

    def record(req, tok):
        rows = seen[-1]
        req.logged = getattr(req, "logged", []) + [rows[0][rows[1]]]
        rows[1] += 1
        append(req, tok)
    eng._append = record
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (37, 21)]
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=7))
    eng.run(max_steps=200)
    for rid, p in enumerate(prompts):
        req = eng.scheduler.requests[rid]
        got = np.stack(req.logged)
        toks = p + req.output[:-1]
        want = R.logits_at(cfg, seed, toks, np.arange(len(p) - 1, len(toks)))
        # float32 serving against the float32 reference: the engine attends
        # in the latent space over pages, sums experts per group; rounding
        assert got.shape == want.shape == (7, cfg["vocab_size"])
        np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(), rtol=0)
        assert req.output == list(np.argmax(want, -1))
    # every real row was routed to top-k experts in each MoE layer
    rows = sum(len(p) + 6 for p in prompts)
    assert eng.expert_counts()[0].sum() == rows * 2 * 2


def test_counts_at_published_widths():
    cfg = FILE
    d, H, E, K, fe = 2048, 16, 64, 6, 1408
    attn = d * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    dense = attn + 3 * d * 10944
    moe = attn + d * E + 3 * d * (K * fe + 2 * fe)
    assert flops.layer_matmul_params(cfg) == (dense + 8 * moe) / 9
    # one decode row over 100 keys, a 128-token chunk at positions 512..639
    q, kv = (1, 128), (100, 640)
    keys = 100 + sum(range(513, 641))
    assert flops.attention_flops(cfg, q, kv) == 2 * H * (576 + 512) * keys
    pages = 7 + 40
    assert flops.attention_bytes(cfg, q, kv, 16) == 2 * (
        pages * 16 * 576 + 129 * H * (512 + 64 + 512))


def test_harness_serves_the_cell_at_small_widths(capsys):
    """``bench/run.py`` end to end on the CPU, traced, with the cell's own
    readers (which find no chip kernel here): a sound run is correct, and
    the expert counters reach the window's record."""
    from bench import run
    from bench.tests import tiny

    cfg = dict(small(), engine=dict(tiny.CFG["engine"], max_len=256))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    specs = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    cell = {"name": "mla.cell", "config": "mla", "traffic": "tiny", "chips": 1}
    layer = ["mla_paged_attention_roofline", "moe_experts_roofline", "moe_share"]
    loaded = (cell, cfg, tiny.MIX, tiny.CELL_DATA, specs,
              ["setup_s", "itl_p95_s", "output_tokens_per_s"], layer)
    seen = []
    real = run.Run
    run.Run = lambda **kw: seen.append(real(**kw)) or seen[-1]
    try:
        rc = run.main(["--workload", "mla.cell", "--seed", str(2**34 + 3), "--seconds", "3",
                       "--trace", "1"], require_tpu=False,
                      mc=program(cfg, jnp.bfloat16).cfg, loaded=loaded)
    finally:
        run.Run = real
    res = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and res["correct"], res
    (r,) = seen
    routed = r.counters["moe.routed_rows"]
    assert routed > 0 and routed == sum(r.counters[f"moe.expert_rows.{e}"] for e in range(8))
    assert 0 < r.counters["moe.experts_hit"] <= 8 * 2 * r.span_n["engine.step"]
    assert r.trace is not None and r.scope_s is not None


def test_file_is_the_program_cut_to_nine_layers():
    mc = engine_build.program_config(FILE)
    assert (mc.n_layers, mc.n_prefix_layers, mc.n_periods) == (9, 1, 8)
    assert (mc.q_lora_rank, mc.n_experts, mc.top_k, mc.moe_d_ff) == (0, 64, 6, 1408)
    assert "head_dim" not in FILE  # as the published config.json


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 1024), ("kv_lora_rank", 256), ("q_lora_rank", 1536),
    ("moe_intermediate_size", 704), ("n_routed_experts", 32),
    ("num_experts_per_tok", 8), ("n_shared_experts", 1), ("rope_theta", 1e6)])
def test_file_with_one_width_changed_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        engine_build.program_config(dict(FILE, **{key: value}))


def test_experts_roofline_reads_any_moe_file():
    """``moe_experts_roofline`` takes its widths and MoE layers from the
    file's own keys: the DeepSeek file (dense layer 0, then 8 MoE layers) and
    a Qwen-MoE-style file with no latent keys, all layers sparse."""
    from types import SimpleNamespace

    from bench.metrics import moe_experts_roofline as M

    qwen = {"num_hidden_layers": 4, "hidden_size": 2048, "moe_intermediate_size": 768,
            "num_experts_per_tok": 8, "decoder_sparse_step": 1, "mlp_only_layers": []}
    assert M.moe_layers(FILE) == 8 and M.moe_layers(qwen) == 4
    assert M.moe_layers(dict(qwen, mlp_only_layers=[0])) == 3
    step = SimpleNamespace(q_lens=(1, 1, 128))
    for cfg, n_moe in ((FILE, 8), (qwen, 4)):
        run = SimpleNamespace(
            cfg=cfg, scope_s={"moe_experts": 0.5}, span_n={"engine.step": 10},
            counters={"moe.experts_hit": 10 * n_moe * 64}, traced_steps=[step] * 4,
            peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
        d, fe, K = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
        routed = K * n_moe * 130 * 4
        b = 2.0 * (3 * d * fe * n_moe * 64 * 4 + 3 * (d + fe) * routed)
        f = 6.0 * d * fe * routed
        assert M.read(run) == pytest.approx(100 * max(f / 197e12, b / 819e9) / 0.5)
