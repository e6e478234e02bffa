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
  k/v_pages:    (L, P, KV, page, d) page pool stacked over layers, left
                                   in HBM; the engine allocates KV in this
                                   shape directly (physically paged — pages
                                   are relocatable, ids arbitrary) and the
                                   tables carry the block allocator's real
                                   page ids. KV sits outside the last two
                                   axes, so one page of one head is one
                                   contiguous (page, d) tile.
  layer:        int32 scalar       the stack's layer to read, scalar-
                                   prefetched; without it an unstacked
                                   (P, KV, page, d) pool is read as a
                                   stack of one
  cu_q_lens:    (S+1,) int32       row offsets of each segment
  kv_lens:      (S,) int32         keys the segment's last row attends
  block_tables: (S, nb) int32      per-segment page ids, logical order;
                                   entries past ceil(kv_len/page) are never
                                   read

Grid is (S, KV, nb / bp): each step covers one KV block of bp pages of one
head, bp = 128 // page (at least 1, at most nb; 8 at page 16), so a block's
scores fill the 128 lanes of a vreg tile whatever the page size. Pages are
not fetched by BlockSpec index maps: the kernel copies a block's pages
itself (`make_async_copy` of `pool[layer, table[s, p], h]`) into one slot
of a 2-slot VMEM buffer, starting block ib+1's copies before block ib's compute.
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
    layer_ref,  # scalar prefetch (1,)
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
    layer = layer_ref[0]
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
                        pool.at[layer, pid, h], buf.at[slot, i], sems.at[slot])
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
    q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables, layer=None,
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
    order; k/v_pages: (L, P, KV, page, d) stacked over layers, of which
    ``layer`` (traced int32 scalar) is read, or (P, KV, page, d) with no
    ``layer``; cu_q_lens: (S+1,) int32 row offsets; kv_lens: (S,) int32
    keys the segment's last row attends; block_tables: (S, nb) int32
    per-segment page ids. ``qb`` is the static q-block row
    count — a pow2 bucket of the longest segment (the engine buckets it
    alongside nb and S so the jit cache stays bounded). Rows past
    cu_q_lens[-1] are padding and come back zero; segments with q_len == 0
    are skipped entirely.
    """
    N, KV, G, d = q.shape
    S = kv_lens.shape[0]
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    page = k_pages.shape[3]
    nb = block_tables.shape[1]
    scale = 1.0 / d**0.5
    grid = mixed_grid(S, KV, nb, page)
    bp = kv_block_pages(nb, page)
    q_lens, seg, j = _segment_rows(cu_q_lens, N, S)
    q_seg = _to_segments(q, seg, j, S, qb)
    tables = _whole_blocks(block_tables, grid[2] * bp)

    kernel = functools.partial(
        _mixed_kernel, scale=scale, window=window, softcap_val=softcap,
        page=page, bp=bp, g=G,
    )
    q_spec = pl.BlockSpec((1, None, qb * G, d),
                          lambda s, h, ib, qls, kls, tabs, lay: (s, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
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
        q_lens, kv_lens.astype(jnp.int32), tables, layer, q_seg, k_pages, v_pages,
    )
    return _from_segments(o_seg, seg, j, qb)  # (N, KV, G, d)


def _stacked(a, b, layer):
    """(a, b, layer as (1,) int32): pools stacked over layers as given, or
    unstacked pools (``layer`` None) as a stack of one, read at layer 0 —
    a leading unit axis, which costs no copy."""
    if layer is None:
        a, b, layer = a[None], b[None], 0
    return a, b, jnp.reshape(layer, (1,)).astype(jnp.int32)


def _segment_rows(cu_q_lens, N: int, S: int):
    """(q_lens (S,), seg (N,), j (N,)): each packed row's segment (S for
    padding rows past ``cu_q_lens[-1]``) and chunk-local index."""
    cu = cu_q_lens.astype(jnp.int32)
    q_lens = cu[1:] - cu[:-1]
    row = jnp.arange(N, dtype=jnp.int32)
    seg = jnp.searchsorted(cu, row, side="right") - 1  # S for padding rows
    return q_lens, seg, row - cu[jnp.clip(seg, 0, S)]


def _to_segments(q, seg, j, S: int, qb: int):
    """Flat rows (N, KV, G, d) -> per-segment head-major q-blocks
    (S, KV, qb*G, d): segment s's chunk-local token j lands in rows
    [j*G, (j+1)*G) of every head's block; padding rows scatter into the
    throwaway S-th slot (dropped here), tail rows past qb are dropped by
    the scatter's out-of-bounds semantics."""
    _, KV, G, d = q.shape
    q_seg = jnp.zeros((S + 1, qb, KV, G, d), q.dtype)
    q_seg = q_seg.at[jnp.clip(seg, 0, S), j].set(q)
    return q_seg[:S].transpose(0, 2, 1, 3, 4).reshape(S, KV, qb * G, d)


def _from_segments(o_seg, seg, j, qb: int):
    """Per-segment blocks (S, KV, qb*G, d) back to flat rows (N, KV, G, d):
    padding rows clamp into some segment's tail and are discarded by the
    caller, like every packed padding row."""
    S, KV, rows, d = o_seg.shape
    o_r = o_seg.reshape(S, KV, qb, rows // qb, d)
    return o_r[jnp.clip(seg, 0, S - 1), :, jnp.clip(j, 0, qb - 1)]


def _whole_blocks(block_tables, cols: int):
    """The tables padded to ``cols`` columns (whole KV blocks); the added
    columns lie past every segment's length, so no page is ever fetched
    through them."""
    tables = block_tables.astype(jnp.int32)
    if cols != tables.shape[1]:
        tables = jnp.pad(tables, ((0, 0), (0, cols - tables.shape[1])))
    return tables


def _mixed_mla_kernel(
    q_lens_ref,  # scalar prefetch (S,)
    kv_lens_ref,  # scalar prefetch (S,)
    tables_ref,  # scalar prefetch (S, nbb * bp)
    layer_ref,  # scalar prefetch (1,)
    ql_ref, qr_ref, c_hbm, r_hbm, o_ref,
    c_buf, r_buf, sems, acc_ref, m_ref, l_ref,
    *, scale, page, bp, g,
):
    """``_mixed_kernel`` for latent attention: one KV "head" whose keys are
    the latent and rope pages side by side and whose values are the latent
    pages again, so each block's pages are copied once and the latent tile
    serves both products. Rows are (chunk token, query head) pairs."""
    s_idx = pl.program_id(0)
    ib = pl.program_id(2)
    nbb = pl.num_programs(2)
    q_len = q_lens_ref[s_idx]
    kv_len = kv_lens_ref[s_idx]
    layer = layer_ref[0]
    blk = bp * page
    live_pages = pl.cdiv(kv_len, page)
    runs = functools.partial(
        _block_runs, blk=blk, kv_len=kv_len, q_len=q_len, window=None)

    def page_copies(b, slot, start):
        for i in range(bp):
            p = b * bp + i

            @pl.when(p < live_pages)
            def _():
                pid = tables_ref[s_idx, p]
                for pool, buf in ((c_hbm, c_buf), (r_hbm, r_buf)):
                    copy = pltpu.make_async_copy(
                        pool.at[layer, pid], buf.at[slot, i], sems.at[slot])
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
        c = c_buf[slot].reshape(blk, -1)  # (blk, L) latent keys and values
        kr = r_buf[slot].reshape(blk, -1)  # (blk, rope)
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql_ref[0], c, nt, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr, nt, preferred_element_type=jnp.float32)
             ) * scale  # (QB*G, blk)
        v = c.astype(jnp.float32)
        v_row = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(v_row < live_pages * page, v, 0.0)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        q_pos = kv_len - q_len + j
        mask = (k_pos <= q_pos) & (j < q_len)
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


# scoped VMEM for the latent kernel: at chunk 128 and 16 heads a q-block is
# 2,048 rows of 512 + 64 queries, with an f32 accumulator of 2,048 x 512
MLA_VMEM_BYTES = 64 * 2**20


@functools.partial(jax.jit, static_argnames=("qb", "scale", "interpret"))
def mixed_paged_mla_attention(
    q_lat, q_rope, c_pages, r_pages, cu_q_lens, kv_lens, block_tables, layer=None,
    *,
    qb: int,
    scale: float,
    interpret: bool = False,
):
    """Latent (MLA) attention for the whole packed step, absorbed form: one
    grid row per segment as in ``mixed_paged_attention``, over the latent
    page pool.

    q_lat: (N, H, L) absorbed queries ``q_nope W_uk``; q_rope: (N, H, rope);
    c_pages: (L, P, page, Wc) latent pool and r_pages: (L, P, page, Wr)
    rope-key pool, stacked over layers of which ``layer`` (traced int32
    scalar) is read, or (P, page, W) each with no ``layer``; each holds
    its L (rope) values in the leading lanes of a whole
    number of 128-lane tiles, so a page is one tile-aligned copy (the
    queries are padded with zeros to match, so a pool's tail, zero in the
    engine's pool, adds nothing); cu_q_lens / kv_lens / block_tables as
    ``mixed_paged_attention``; ``scale`` the softmax scale. Returns the
    (N, H, L) latent outputs (softmax over keys of the latent values), for
    the caller's W_uv."""
    N, H, L = q_lat.shape
    S = kv_lens.shape[0]
    c_pages, r_pages, layer = _stacked(c_pages, r_pages, layer)
    _, _, page, Wc = c_pages.shape
    Wr = r_pages.shape[-1]
    nb = block_tables.shape[1]
    grid = mixed_grid(S, 1, nb, page)
    bp = kv_block_pages(nb, page)
    q_lens, seg, j = _segment_rows(cu_q_lens, N, S)
    pad = lambda a, w: jnp.pad(a, ((0, 0), (0, 0), (0, w - a.shape[-1])))
    q_seg = _to_segments(pad(q_lat, Wc)[:, None], seg, j, S, qb)  # (S, 1, qb*H, Wc)
    r_seg = _to_segments(pad(q_rope, Wr)[:, None], seg, j, S, qb)
    tables = _whole_blocks(block_tables, grid[2] * bp)

    kernel = functools.partial(_mixed_mla_kernel, scale=scale, page=page, bp=bp, g=H)
    block = lambda w: pl.BlockSpec((1, None, qb * H, w),
                                   lambda s, h, ib, qls, kls, tabs, lay: (s, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            block(Wc), block(Wr),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=block(Wc),
        scratch_shapes=[
            pltpu.VMEM((2, bp, page, Wc), c_pages.dtype),
            pltpu.VMEM((2, bp, page, Wr), r_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((qb * H, Wc), jnp.float32),
            pltpu.VMEM((qb * H, LANES), jnp.float32),
            pltpu.VMEM((qb * H, LANES), jnp.float32),
        ],
    )
    o_seg = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, qb * H, Wc), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=MLA_VMEM_BYTES,
        ),
        interpret=interpret,
        name="mixed_paged_mla_attention",
    )(
        q_lens, kv_lens.astype(jnp.int32), tables, layer, q_seg, r_seg, c_pages, r_pages,
    )
    return _from_segments(o_seg, seg, j, qb)[:, 0, :, :L]  # (N, H, L)


def ragged_mixed_mla_attention(
    q_lat, q_rope, c_pages, r_pages, cu_q_lens, kv_lens, block_tables, layer=None,
    *,
    qb: int,
    scale: float,
    use_kernel: bool = False,
    interpret: bool = False,
):
    """Dispatch for latent attention: ``mixed_paged_mla_attention`` on TPU
    (or interpret mode), the jnp oracle ``kernels.ref.mixed_mla_attention_ref``
    on CPU, over ``layer`` of stacked pools (or unstacked pools without
    it)."""
    if use_kernel or interpret:
        return mixed_paged_mla_attention(
            q_lat, q_rope, c_pages, r_pages, cu_q_lens, kv_lens, block_tables, layer,
            qb=qb, scale=scale, interpret=interpret)
    from repro.kernels.ref import mixed_mla_attention_ref

    if layer is not None:
        c_pages, r_pages = c_pages[layer], r_pages[layer]
    return mixed_mla_attention_ref(
        q_lat, q_rope, c_pages, r_pages, cu_q_lens, kv_lens, block_tables, scale=scale)


def ragged_mixed_attention(
    q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables, layer=None,
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
    them once per *chunk* rather than once per token. ``layer`` selects
    one layer of pools stacked over layers, as in the kernel."""
    if use_kernel or interpret:
        return mixed_paged_attention(
            q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables, layer,
            qb=qb, window=window, softcap=softcap, interpret=interpret,
        )
    from repro.kernels.ref import mixed_attention_ref

    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    return mixed_attention_ref(
        q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
        window=window, softcap=softcap,
    )
