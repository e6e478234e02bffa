"""Decode (single-token) attention Pallas kernel with streamed-KV prefetch.

THE op the paper targets: decode attention over a long KV cache is HBM-bound.
The kernel iterates the KV cache block-by-block (grid last dim); Mosaic's
software pipeline double-buffers block n+1's HBM->VMEM DMA underneath block
n's compute — the TPU-native realization of the paper's prefetch overlap at
the capacity real hardware offers (VMEM). The architecture-scale 512MB-buffer
variant (cross-op prefetch) is modelled by the `repro.sim` framework.

Per-request lengths arrive via scalar prefetch (known before the grid runs so
out-of-range KV blocks are skipped — no wasted DMA past a request's length).

q: (B, KV, G, d) one new token per request, grouped-query layout
k/v: (B, KV, S, d) KV cache (padded to S_max)
lengths: (B,) int32 valid tokens per request
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 256
NEG_INF = -1.0e30
LANES = 128


def _decode_kernel(
    lengths_ref,  # scalar prefetch (B,)
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, window, softcap_val, block_k,
):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    length = lengths_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ik * block_k
    run = k_start < length
    if window is not None:
        run &= k_start + block_k > length - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bk)
        if softcap_val is not None:
            s = softcap_val * jnp.tanh(s / softcap_val)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window is not None:
            mask &= k_pos > length - 1 - window  # query position = length-1
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, 1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "block_k", "interpret")
)
def decode_attention(
    q, k, v, lengths,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """q: (B, KV, G, d); k/v: (B, KV, S, d); lengths: (B,) -> (B, KV, G, d)."""
    B, KV, G, d = q.shape
    S = k.shape[2]
    assert S % block_k == 0, (S, block_k)
    scale = 1.0 / d**0.5
    grid = (B, KV, S // block_k)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap_val=softcap, block_k=block_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # index maps receive the scalar-prefetch ref as a trailing arg
            pl.BlockSpec((1, 1, G, d), lambda b, h, ik, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, *_: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, *_: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(lengths, q, k, v)
