"""Mixed-batch ragged block-table (paged) attention Pallas kernel.

The serving engine's packed step mixes decode tokens and prefill-chunk tokens
in one flat row set; every row attends over its *own* context prefix. This
kernel is the vLLM-style paged realization: KV lives in a page pool, each
segment (a prefill chunk or a single decode row) names its pages through a
block table, and per-segment lengths arrive via scalar prefetch so whole
out-of-range pages are skipped — attention cost scales with the tokens a
segment actually owns, not with the padded cache extent.

Layouts (grouped-query heads, head-major so the TPU tiles see whole pages):
  q:            (N, KV, G, d)      one query per packed row
  k/v_pages:    (P, KV, page, d)   page pool; the engine allocates KV in this
                                   shape directly (physically paged — pages
                                   are relocatable, ids arbitrary) and the
                                   tables carry the block allocator's real
                                   page ids. KV sits outside the last two
                                   axes, so one grid step's block is one
                                   head's (page, d) tile — the shape Mosaic
                                   requires (second-minor divisible by 8 or
                                   the whole axis).
  cu_q_lens:    (S+1,) int32       row offsets of each segment
  kv_lens:      (S,) int32         keys the segment's last row attends
  block_tables: (S, nb) int32      per-segment page ids, logical order;
                                   entries past ceil(kv_len/page) must still
                                   be valid page ids (the engine points them
                                   at a scratch page) because index maps run
                                   even for skipped grid steps

Grid is (S, KV, nb); the last dimension streams pages with Mosaic's software
pipeline double-buffering page ib+1's DMA under page ib's compute, with the
block-table indirection in the index map (scalar-prefetched, so the DMA
address is known before the grid step runs). `pl.when` guards skip compute
AND the online-softmax update for pages past a segment's length (and, with
`window`, pages wholly below it).

The block tables are scalar-prefetched into SMEM, which bounds (S, nb): at
S=40 segments, nb=4096 (64K tokens at page 16) compiles for a v5e and
nb=8192 exceeds its 1 MiB SMEM.

`ragged_mixed_attention` dispatches to the kernel (TPU / interpret) or to
the pure-jnp oracle `kernels.ref.mixed_attention_ref` (CPU serving + CI).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
LANES = 128


def tokens_touched(lengths, page: int) -> int:
    """Key tokens a block-granular ragged kernel actually reads:
    sum_i ceil(len_i / page) * page. The dense-gather path reads
    len(lengths) * S_max instead. (Single source of truth lives in
    sim/opcost so kernel, scheduler, and simulator price identically.)"""
    from repro.sim.opcost import kv_tokens_touched

    return kv_tokens_touched(lengths, page)


def _mixed_kernel(
    q_lens_ref,  # scalar prefetch (S,)
    kv_lens_ref,  # scalar prefetch (S,)
    tables_ref,  # scalar prefetch (S, nb)
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, window, softcap_val, page, g,
):
    s_idx = pl.program_id(0)
    ib = pl.program_id(2)
    nb = pl.num_programs(2)
    q_len = q_lens_ref[s_idx]
    kv_len = kv_lens_ref[s_idx]

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ib * page
    # the chunk's oldest query sits at key position kv_len - q_len; pages
    # wholly above kv_len (causal, newest query) or — with a window — wholly
    # below the oldest query's window are skipped for the entire q-block
    run = (k_start < kv_len) & (q_len > 0)
    if window is not None:
        run &= k_start + page > kv_len - q_len - window + 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (QB*G, d)
        k = k_ref[0].astype(jnp.float32)  # (page, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (QB*G, page)
        if softcap_val is not None:
            s = softcap_val * jnp.tanh(s / softcap_val)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # row r holds query g = r % G of chunk-local token j = r // G, whose
        # absolute position is kv_len - q_len + j: intra-chunk causality and
        # the dead tail (j >= q_len) fall out of the same mask
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        q_pos = kv_len - q_len + j
        mask = (k_pos <= q_pos) & (j < q_len)
        if window is not None:
            mask &= k_pos > q_pos - window
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

    @pl.when(ib == nb - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("qb", "window", "softcap", "interpret")
)
def mixed_paged_attention(
    q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
    *,
    qb: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
):
    """One kernel call for the whole packed step: every segment — a prefill
    chunk or a single decode row — is one grid row computed as a causal
    q-block over its paged prefix, so each segment's KV pages are read ONCE
    per chunk instead of once per token.

    q: (N, KV, G, d) flat packed rows, segments contiguous in cu_q_lens
    order; k/v_pages: (P, KV, page, d); cu_q_lens: (S+1,) int32 row offsets;
    kv_lens: (S,) int32 keys the segment's last row attends; block_tables:
    (S, nb) int32 per-segment page ids. ``qb`` is the static q-block row
    count — a pow2 bucket of the longest segment (the engine buckets it
    alongside nb and S so the jit cache stays bounded). Rows past
    cu_q_lens[-1] are padding and come back zero; segments with q_len == 0
    are skipped entirely.
    """
    N, KV, G, d = q.shape
    S = kv_lens.shape[0]
    page = k_pages.shape[2]
    nb = block_tables.shape[1]
    scale = 1.0 / d**0.5

    cu = cu_q_lens.astype(jnp.int32)
    q_lens = cu[1:] - cu[:-1]
    row = jnp.arange(N, dtype=jnp.int32)
    seg = jnp.searchsorted(cu, row, side="right") - 1  # S for padding rows
    j = row - cu[jnp.clip(seg, 0, S)]

    # per-segment head-major q-block (S, KV, qb*G, d): segment s's
    # chunk-local token j lands in rows [j*G, (j+1)*G) of every head's
    # block; padding rows scatter into the throwaway S-th slot (dropped
    # below), tail rows past qb are dropped by the scatter's out-of-bounds
    # semantics
    q_seg = jnp.zeros((S + 1, qb, KV, G, d), q.dtype)
    q_seg = q_seg.at[jnp.clip(seg, 0, S), j].set(q)
    q_seg = q_seg[:S].transpose(0, 2, 1, 3, 4).reshape(S, KV, qb * G, d)

    kernel = functools.partial(
        _mixed_kernel, scale=scale, window=window, softcap_val=softcap,
        page=page, g=G,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, KV, nb),
        in_specs=[
            pl.BlockSpec((1, None, qb * G, d),
                         lambda s, h, ib, qls, kls, tabs: (s, h, 0, 0)),
            pl.BlockSpec((1, None, page, d),
                         lambda s, h, ib, qls, kls, tabs: (tabs[s, ib], h, 0, 0)),
            pl.BlockSpec((1, None, page, d),
                         lambda s, h, ib, qls, kls, tabs: (tabs[s, ib], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, None, qb * G, d),
                               lambda s, h, ib, qls, kls, tabs: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qb * G, d), jnp.float32),
            pltpu.VMEM((qb * G, LANES), jnp.float32),
            pltpu.VMEM((qb * G, LANES), jnp.float32),
        ],
    )
    o_seg = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, qb * G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mixed_paged_attention",
    )(
        q_lens, kv_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
        q_seg, k_pages, v_pages,
    )
    # back to flat rows: padding rows clamp into some segment's tail and are
    # discarded by the caller, like every packed padding row
    o_r = o_seg.reshape(S, KV, qb, G, d)
    return o_r[jnp.clip(seg, 0, S - 1), :, jnp.clip(j, 0, qb - 1)]  # (N, KV, G, d)


def ragged_mixed_attention(
    q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
    *,
    qb: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    use_kernel: bool = False,
    interpret: bool = False,
):
    """Dispatch for the unified mixed-batch path: Pallas kernel on TPU (or
    interpret mode), per-token-expansion jnp oracle on CPU. Both read each
    segment's pages bounded to its own context; the kernel additionally reads
    them once per *chunk* rather than once per token."""
    if use_kernel or interpret:
        return mixed_paged_attention(
            q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
            qb=qb, window=window, softcap=softcap, interpret=interpret,
        )
    from repro.kernels.ref import mixed_attention_ref

    return mixed_attention_ref(
        q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
        window=window, softcap=softcap,
    )
