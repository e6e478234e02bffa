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
  k/v_pages:    (P, KV, page, d)   page pool, left in HBM; the engine
                                   allocates KV in this shape directly
                                   (physically paged — pages are
                                   relocatable, ids arbitrary) and the
                                   tables carry the block allocator's real
                                   page ids. KV sits outside the last two
                                   axes, so one page of one head is one
                                   contiguous (page, d) tile.
  cu_q_lens:    (S+1,) int32       row offsets of each segment
  kv_lens:      (S,) int32         keys the segment's last row attends
  block_tables: (S, nb) int32      per-segment page ids, logical order;
                                   entries past ceil(kv_len/page) are never
                                   read

Grid is (S, KV, nb / bp): each step covers one KV block of bp pages of one
head, bp = 128 // page (at least 1, at most nb; 8 at page 16), so a block's
scores fill the 128 lanes of a vreg tile whatever the page size. Pages are
not fetched by BlockSpec index maps: the kernel copies a block's pages
itself (`make_async_copy` of `pool[table[s, p], h]`) into one slot of a
2-slot VMEM buffer, starting block ib+1's copies before block ib's compute.
`pl.when` guards skip the copies, the compute AND the online-softmax update
for blocks past a segment's length (and, with `window`, blocks wholly below
it); inside a live block, pages past the segment's length are not fetched
and their keys are masked.

The block tables are scalar-prefetched into SMEM, which bounds (S, nb): at
S=40 segments, nb=4096 (64K tokens at page 16) compiles for a v5e and
nb=8192 exceeds its 1 MiB SMEM.

`ragged_mixed_attention` dispatches to the kernel (TPU / interpret) or to
the pure-jnp oracle `kernels.ref.mixed_attention_ref` (CPU serving + CI).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

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


def kv_block_pages(nb: int, page: int) -> int:
    """Pages in one grid step's KV block: enough to fill the 128 key lanes
    of a score tile (``128 // page``, at least one), capped at the table's
    ``nb`` columns."""
    return max(1, min(LANES // page, nb))


def mixed_grid(S: int, KV: int, nb: int, page: int) -> Tuple[int, int, int]:
    """Grid of one ``mixed_paged_attention`` call over ``S`` segments,
    ``KV`` heads and ``(S, nb)`` block tables of ``page``-token pages: one
    step per (segment, KV head, block of ``kv_block_pages`` pages). Its
    product is the call's grid-step count."""
    return S, KV, -(-nb // kv_block_pages(nb, page))


def _block_runs(b, *, blk, kv_len, q_len, window):
    """Whether KV block ``b`` (keys ``[b*blk, (b+1)*blk)``) holds a key some
    query of the segment attends. The chunk's oldest query sits at key
    position kv_len - q_len; blocks wholly above kv_len (causal, newest
    query) or — with a window — wholly below the oldest query's window are
    skipped for the entire q-block, so the running blocks are one
    contiguous run. Takes Python ints or traced scalars."""
    run = (b * blk < kv_len) & (q_len > 0)
    if window is not None:
        run &= (b + 1) * blk > kv_len - q_len - window + 1
    return run


def _block_copies(ib, nbb, runs):
    """(own, next): whether grid step ``ib`` of ``nbb`` starts its own
    block's page copies, and block ib+1's (under block ib's compute, also
    from a skipped step). Only block 0 fetches itself, so every running
    block of a (segment, head) is started exactly once and waited on by its
    own step, and nothing is started for a block that is skipped."""
    return runs(ib) & (ib == 0), (ib + 1 < nbb) & runs(ib + 1)


def _mixed_kernel(
    q_lens_ref,  # scalar prefetch (S,)
    kv_lens_ref,  # scalar prefetch (S,)
    tables_ref,  # scalar prefetch (S, nbb * bp)
    q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
    *, scale, window, softcap_val, page, bp, g,
):
    s_idx = pl.program_id(0)
    h = pl.program_id(1)
    ib = pl.program_id(2)
    nbb = pl.num_programs(2)
    q_len = q_lens_ref[s_idx]
    kv_len = kv_lens_ref[s_idx]
    blk = bp * page
    live_pages = pl.cdiv(kv_len, page)
    runs = functools.partial(
        _block_runs, blk=blk, kv_len=kv_len, q_len=q_len, window=window)

    def page_copies(b, slot, start):
        # block b's live pages, one head's (page, d) tile each, into `slot`;
        # pages past the segment's length are neither fetched nor waited on
        for i in range(bp):
            p = b * bp + i

            @pl.when(p < live_pages)
            def _():
                pid = tables_ref[s_idx, p]
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    copy = pltpu.make_async_copy(
                        pool.at[pid, h], buf.at[slot, i], sems.at[slot])
                    if start:
                        copy.start()
                    else:
                        copy.wait()

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slot = ib % 2
    own, nxt = _block_copies(ib, nbb, runs)

    @pl.when(own)
    def _fetch():
        page_copies(ib, slot, start=True)

    @pl.when(nxt)
    def _prefetch():
        page_copies(ib + 1, 1 - slot, start=True)

    @pl.when(runs(ib))
    def _compute():
        page_copies(ib, slot, start=False)
        k_start = ib * blk
        q = q_ref[0].astype(jnp.float32)  # (QB*G, d)
        k = k_buf[slot].reshape(blk, -1).astype(jnp.float32)  # (blk, d)
        v = v_buf[slot].reshape(blk, -1).astype(jnp.float32)
        # rows of unfetched pages hold whatever the slot held before; their
        # keys are masked below, and zeroing their values keeps p·V finite
        v_row = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(v_row < live_pages * page, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (QB*G, blk)
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

    @pl.when(ib == nbb - 1)
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
    grid = mixed_grid(S, KV, nb, page)
    bp = kv_block_pages(nb, page)

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

    # whole blocks of table columns; the added columns lie past every
    # segment's length, so no page is ever fetched through them
    tables = block_tables.astype(jnp.int32)
    if grid[2] * bp != nb:
        tables = jnp.pad(tables, ((0, 0), (0, grid[2] * bp - nb)))

    kernel = functools.partial(
        _mixed_kernel, scale=scale, window=window, softcap_val=softcap,
        page=page, bp=bp, g=G,
    )
    q_spec = pl.BlockSpec((1, None, qb * G, d),
                          lambda s, h, ib, qls, kls, tabs: (s, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, bp, page, d), k_pages.dtype),
            pltpu.VMEM((2, bp, page, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
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
        q_lens, kv_lens.astype(jnp.int32), tables, q_seg, k_pages, v_pages,
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
