"""Build the served engine for a configuration file, as ``launch.serve``
does: the registered architecture cut in depth only, bf16 weights drawn on
the device from the seed, the KV page pool sized from the device's free
memory, the same ``SchedulerConfig`` fields; then compile every step shape
the cell's traffic reaches before the measured window opens.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

# configuration-file key -> ModelConfig field the program must agree on
WIDTHS = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers", "qk_norm": "qk_norm",
    "attention_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
}


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def program_config(cfg: dict):
    """The program's registered architecture, cut to the file's depth; every
    width must equal the file's, or the run stops."""
    from repro.configs import get_config
    from repro.configs.reduced import cut_depth

    mc = get_config(cfg["arch"])
    if mc.n_layers != cfg["num_hidden_layers"]:
        mc = cut_depth(mc, cfg["num_hidden_layers"])
    check_widths(cfg, mc)
    return mc


def check_widths(cfg: dict, mc) -> None:
    bad = {k: (cfg[k], getattr(mc, f)) for k, f in WIDTHS.items()
           if k in cfg and cfg[k] != getattr(mc, f)}
    if bad:
        raise ValueError(f"{cfg['name']}: program config differs from the file "
                         f"(file, program): {bad}")


def build(cfg: dict, seed: int, mc=None):
    """(engine, pool pages, pool basis). Weights come from the configuration's
    reference module (``draw_params``), checked leaf by leaf against the
    shapes and types the program's own ``init`` would make."""
    from repro.core.scheduler import SchedulerConfig
    from repro.launch.serve import device_kv_pool_blocks
    from repro.models import build_model
    from repro.serving.engine import Engine

    mc = mc if mc is not None else program_config(cfg)
    e = cfg["engine"]
    model = build_model(mc, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = reference(cfg).draw_params(cfg, seed)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got_s = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want_s = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got_s != want_s:
        raise ValueError(f"{cfg['name']}: drawn weights do not match the "
                         "program's parameter layout")
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if "bytes_limit" in stats:
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        pages, basis = device_kv_pool_blocks(
            mc, free, e["max_batch"] + e["chunk"], e["max_batch"], e["max_len"],
            e["kv_block"])
    else:  # a device without memory stats: the dense layout's capacity
        pages, basis = e["max_batch"] * e["max_len"] // e["kv_block"], "dense"
    if isinstance(e.get("kv_pages_cap"), int) and pages > e["kv_pages_cap"]:
        pages, basis = e["kv_pages_cap"], "cap"
    sc = SchedulerConfig(
        chunk_size=e["chunk"], max_decode_batch=e["max_batch"],
        prefetch_buffer_bytes=int(e["prefetch_mb"] * 2**20),
        max_concurrent_prefills=e["max_prefills"], policy=e["policy"],
        preemption=e["preemption"], kv_block_size=e["kv_block"],
        num_kv_blocks=pages, enable_prefix_cache=e["prefix_cache"],
        async_prefetch=e["async_prefetch"])
    eng = Engine(model, params, sc, max_len=e["max_len"],
                 cache_dtype=jnp.bfloat16, attn_kernel="auto")
    return eng, pages, basis


def pow2_range(lo: int, hi: int):
    """Powers of two from the bucket holding ``lo`` up to ``hi`` (inclusive)."""
    b = 1
    while b < lo:
        b *= 2
    out = []
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def buckets(eng, cfg: dict, cell: dict):
    """The (nb, sb, qb) step shapes to compile before the window: every
    q-block up to the chunk, and the table widths and segment counts from
    the cell's steady-state floors up to what the engine can form. The
    engine buckets each as a power of two with a floor of 8 (nb, sb)."""
    e, w = cfg["engine"], cell["prewarm"]
    nb = pow2_range(max(8, -(-w["min_context"] // e["kv_block"])), eng.pages_per_slot)
    if not nb or nb[-1] < eng.pages_per_slot:
        nb.append(eng.pages_per_slot)
    sb = pow2_range(max(8, w["min_segments"]), max(8, e["max_batch"]))
    if sb[-1] < e["max_batch"]:
        sb.append(sb[-1] * 2)
    qb = pow2_range(1, e["chunk"])
    return nb, sb, qb


def shape_of(eng, q_lens, kv_lens):
    """The (nb, sb, qb) bucket the engine compiles a step with this segment
    layout under (``Engine._run_packed``'s bucketing)."""
    nb = eng._nb_bucket(max(kv_lens, default=1))
    sb = max(8, 1 << max(len(q_lens) - 1, 0).bit_length())
    qb = 1 << max(max(q_lens, default=1) - 1, 0).bit_length()
    return nb, sb, qb


def prewarm(eng, nb_set, sb_set, qb_set) -> int:
    """Run one empty step per (nb, sb, qb): every row and segment points at
    the scratch slot, so nothing but the scratch page is written. Also
    compiles the sampled-row gathers for 1..n_slots rows and the page-copy
    mover's smallest bucket. Returns the number of step shapes."""
    n, slot = eng.bucket, eng.n_slots
    tok = jnp.zeros((n,), jnp.int32)
    slots = jnp.full((n,), slot, jnp.int32)
    logits = None
    for nb in nb_set:
        bt = jnp.asarray(np.full((slot + 1, nb), eng._scratch_page, np.int32))
        for sb in sb_set:
            cu = jnp.zeros((sb + 1,), jnp.int32)
            kl = jnp.zeros((sb,), jnp.int32)
            ss = jnp.full((sb,), slot, jnp.int32)
            for qb in qb_set:
                logits, eng.cache = eng._packed(eng.params, eng.cache, tok, slots,
                                                tok, bt, cu, kl, ss, qb)
    for k in range(1, slot + 1):
        np.asarray(logits[jnp.asarray(list(range(k)), jnp.int32)])
    scratch = jnp.full((8,), eng._scratch_page, jnp.int32)
    eng.cache = eng._copy_pages(eng.cache, scratch, scratch)
    jax.block_until_ready(eng.cache)
    return len(nb_set) * len(sb_set) * len(qb_set)
