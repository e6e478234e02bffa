"""A tiny dense-GQA cell for the harness's CPU tests: same code paths as the
real cells (engine, driver, metrics, reference), widths small enough for the
CPU, every step shape compiled in a few seconds."""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CFG = {
    "name": "tiny", "source": "test", "arch": "tiny", "reference": "dense_gqa",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "attention_bias": True, "qk_norm": True, "tie_word_embeddings": False,
    "weights": {"embed_std": 1.0, "head_std": 0.1, "q_scale": 4.0,
                "out_scale": 0.5, "bias_std": 0.1, "norm_jitter": 0.2},
    "engine": {"max_batch": 4, "max_len": 256, "chunk": 32, "kv_block": 16,
               "max_prefills": 1, "policy": "fcfs", "preemption": "recompute",
               "prefix_cache": True, "async_prefetch": True, "prefetch_mb": 0.25},
}
MIX = {"kind": "independent", "prompt": {"median": 40, "p90": 90},
       "output": {"median": 30, "p90": 60},
       "arrival": {"process": "backlog", "count": 512},
       "warmup": {"completions": 2, "max_s": 60}}
CELL_DATA = {"prewarm": {"min_context": 128, "min_segments": 8},
             "check_tokens": 100, "check_requests": 6, "logit_gap_limit": 0.05}


def model_config():
    from repro.configs.base import ModelConfig

    c = CFG
    return ModelConfig(
        name="tiny", family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=c["qk_norm"], qkv_bias=c["attention_bias"],
        rope_theta=c["rope_theta"], tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"])


def loaded(mix=None):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    specs = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    cell = {"name": "tiny.cell", "config": "tiny", "traffic": "tiny", "chips": 1}
    e2e = ["setup_s", "itl_p95_s", "output_tokens_per_s"]
    layer = ["sched_ms_per_step", "compiles_in_window"]
    return cell, CFG, mix or MIX, CELL_DATA, specs, e2e, layer
