"""Ahead-of-time compiles for a described TPU v5e: the served path's kernel
and step at real widths, refused here exactly as the chip's compiler would
refuse them (tiling, VMEM/SMEM, HBM fit). Nothing runs; no chip is needed.

The topology is described inside a fixture — never at import, in a
``skipif`` or in ``parametrize`` — so only the worker that runs this file
loads the TPU compiler, and every worker collects the same tests.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import mixed_paged_attention

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a described-device compile cannot be read back without a chip; keep
    # the persistent cache out of it so nothing is written or warned about
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# (KV, G, d, page, qb, S segments, nb table columns, window, softcap)
CASES = {
    "llama8b_decode": (8, 4, 128, 16, 1, 8, 512, None, None),
    "llama8b_mixed": (8, 4, 128, 16, 32, 16, 512, None, None),
    "llama8b_nb2048": (8, 4, 128, 16, 32, 40, 2048, None, None),
    "gemma2b_window_softcap": (4, 2, 256, 16, 32, 16, 512, 4096, 50.0),
    "qwen3_32b_mixed": (8, 8, 128, 16, 128, 8, 1024, None, None),
    "qwen3_32b_nb2048": (8, 8, 128, 16, 128, 8, 2048, None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_mixed_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    KV, G, d, page, qb, S, nb, window, softcap = CASES[case]
    N, P = 520, 4097
    f = jax.jit(lambda q, k, v, cu, kl, bt: mixed_paged_attention(
        q, k, v, cu, kl, bt, qb=qb, window=window, softcap=softcap))
    bf16, i32 = jnp.bfloat16, jnp.int32
    compiled = f.lower(
        _sds(one_chip, (N, KV, G, d), bf16), _sds(one_chip, (P, KV, page, d), bf16),
        _sds(one_chip, (P, KV, page, d), bf16), _sds(one_chip, (S + 1,), i32),
        _sds(one_chip, (S,), i32), _sds(one_chip, (S, nb), i32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# DeepSeek-V2-Lite's latent kernel: (qb, S segments, nb table columns)
MLA_CASES = {"decode": (1, 8, 512), "chunk_nb2048": (128, 8, 2048)}


@pytest.mark.parametrize("case", sorted(MLA_CASES))
def test_mla_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    """16 heads, latent 512 and rope 64 (in a 128-lane pool), 16-token
    pages: a chunk's q-block is 2,048 rows of 512 + 128 queries."""
    from repro.kernels.paged_attention import mixed_paged_mla_attention

    qb, S, nb = MLA_CASES[case]
    N, P, H = 136, 16385, 16
    f = jax.jit(lambda ql, qr, c, r, cu, kl, bt: mixed_paged_mla_attention(
        ql, qr, c, r, cu, kl, bt, qb=qb, scale=0.1147))
    bf16, i32 = jnp.bfloat16, jnp.int32
    compiled = f.lower(
        _sds(one_chip, (N, H, 512), bf16), _sds(one_chip, (N, H, 64), bf16),
        _sds(one_chip, (P, 16, 512), bf16), _sds(one_chip, (P, 16, 128), bf16),
        _sds(one_chip, (S + 1,), i32), _sds(one_chip, (S,), i32),
        _sds(one_chip, (S, nb), i32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("glu", [True, False])
def test_moe_grouped_matmul_compiles_for_v5e(glu, one_chip, no_compile_cache):
    """DeepSeek-V2-Lite's experts: 64 of 2048 x 1408 in each of 8 stacked
    layers, the 136 x 6 routed rows of a chunked step in 16-row tiles (111
    tiles); gate and up together, or down alone."""
    from repro.kernels.grouped_matmul import moe_grouped_matmul

    L, E, d, f, T = 8, 64, 2048, 1408, 111
    K, N = (d, f) if glu else (f, d)
    n_w = 2 if glu else 1
    g = jax.jit(lambda x, ws, te, n, lay: moe_grouped_matmul(
        x, ws, te, n, lay, act=jax.nn.silu if glu else None))
    bf16, i32 = jnp.bfloat16, jnp.int32
    compiled = g.lower(
        _sds(one_chip, (T * 16, K), bf16), (_sds(one_chip, (L, E, K, N), bf16),) * n_w,
        _sds(one_chip, (T,), i32), _sds(one_chip, (1,), i32),
        _sds(one_chip, (1,), i32)).compile()
    assert "moe_grouped_matmul" in compiled.as_text()


def _compile_step(one_chip, arch, n_layers, pages, n_slots, chunk, sb, nb):
    """(compiled, cache): the served paged step of ``arch`` cut to
    ``n_layers`` at published widths, bf16, over a ``pages``-page pool of 16
    tokens, with the cache donated as the engine donates it."""
    from repro.configs import get_config
    from repro.configs.reduced import cut_depth
    from repro.core.packed_step import PagedView, packed_step
    from repro.models import build_model
    from repro.serving.engine import _init_page_pool

    model = build_model(cut_depth(get_config(arch), n_layers),
                        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: _init_page_pool(model, pages, 16, jnp.bfloat16)))
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    step = jax.jit(
        lambda p, c, t, s, pos, bt, cq, kl, ss: packed_step(
            model, p, c, t, s, pos,
            paged=PagedView(bt, 16, use_kernel=True, cu_q_lens=cq, kv_lens=kl,
                            seg_slots=ss, q_block=chunk)),
        donate_argnums=(1,))
    n = n_slots + chunk
    compiled = step.lower(params, cache, i32(n), i32(n), i32(n),
                          i32(n_slots + 1, nb), i32(sb + 1), i32(sb), i32(sb)).compile()
    return compiled, cache


def _peak(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_packed_step_fits_one_v5e(one_chip, no_compile_cache):
    """The chip smoke's served step: Llama3.1-8B widths, 16 layers, bf16,
    a 4096-page pool of 16 tokens. The pool is donated and updated in place
    (aliased, not copied), and weights + pool + temporaries fit 16 GiB."""
    compiled, cache = _compile_step(one_chip, "llama3.1-8b", 16, 4097, n_slots=8,
                                    chunk=512, sb=16, nb=512)
    assert "mixed_paged_attention" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= pool_bytes
    peak = _peak(mem)
    assert peak < V5E_HBM, peak


# the benchmark cells' steps: (arch, layers, pool pages, slots, chunk,
# segments, table columns)
CELL_STEPS = {
    "qwen3-32b-l8": ("qwen3-32b", 8, 7001, 8, 128, 16, 1024),
    "deepseek-v2-lite-l9": ("deepseek-v2-lite", 9, 16385, 8, 128, 16, 2048),
}
# ops that move a whole array: a copy, or a slice out of / an update into it
_MOVES = re.compile(r"= \w+\[([\d,]+)\][^ ]* (?:[\w-]*(?:copy|dynamic-slice|"
                    r"dynamic-update-slice)[\w.-]*|fusion\(.*kind=kLoop)")


@pytest.mark.parametrize("cell", sorted(CELL_STEPS))
def test_packed_step_keeps_the_pool_stacked(cell, one_chip, no_compile_cache):
    """The benchmark cells' steps keep the KV pool stacked over layers: no
    copy, slice or update-slice has a layer's pool or the stack for its
    result (the step's K/V are scattered into the stack in place), the
    temporaries are smaller than one layer's K (latent) pool, the pool
    stays aliased, and the step fits 16 GiB."""
    arch, n_layers, pages, n_slots, chunk, sb, nb = CELL_STEPS[cell]
    compiled, cache = _compile_step(one_chip, arch, n_layers, pages, n_slots, chunk,
                                    sb, nb)
    pools = [a.shape for a in jax.tree.leaves(cache)]
    pool_shapes = {",".join(map(str, s)) for p in pools for s in (p, p[1:])}
    moved = [line.strip()[:160] for line in compiled.as_text().splitlines()
             if (m := _MOVES.search(line)) and m.group(1) in pool_shapes]
    assert not moved, moved
    mem = compiled.memory_analysis()
    first = jax.tree.leaves(cache["periods"])[0]  # K, or the latent pool
    one_layer = first.size // first.shape[0] * first.dtype.itemsize
    assert mem.temp_size_in_bytes < one_layer, (mem.temp_size_in_bytes, one_layer)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert _peak(mem) < V5E_HBM, _peak(mem)
