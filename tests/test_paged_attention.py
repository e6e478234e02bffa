"""Ragged block-table (paged) attention: kernel parity + engine integration.

Parity: the Pallas kernel (interpret mode) and the jnp oracle must match the
dense decode-attention oracle across ragged lengths, window, softcap, GQA
group sizes, and permuted (non-contiguous) block tables. Integration: the
packed engine with the paged path (the default) must stay token-identical to
both the dense-gather engine and the serial per-request reference, and the
engine's block-table mirror must track alloc/free/swap transitions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, st
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config, reduce_config
from repro.core.scheduler import SchedulerConfig
from repro.kernels import ops, ref
from repro.kernels.paged_attention import (
    _block_copies, _block_runs, kv_block_pages, mixed_grid, tokens_touched,
)
from repro.models import build_model
from repro.serving.engine import Engine
from repro.serving.request import Request


def rand(rng, shape, dtype=jnp.float32):
    return jax.random.normal(rng, shape, jnp.float32).astype(dtype)


def dense_to_pool(k, page):
    """(B, S, KV, d) slot cache -> (B*S/page, KV, page, d) head-major page
    pool + identity block tables (B, S/page)."""
    B, S, KV, d = k.shape
    pps = S // page
    pool = k.reshape(B * pps, page, KV, d).transpose(0, 2, 1, 3)
    tables = (np.arange(B)[:, None] * pps + np.arange(pps)[None, :]).astype(np.int32)
    return pool, jnp.asarray(tables)


# ---------------------------------------------------------------------------
# parity vs the dense decode oracle (identity tables)
# ---------------------------------------------------------------------------


# MHA / GQA 4x, 6x, 8x / MQA
@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (12, 2), (16, 2), (4, 1)])
@pytest.mark.parametrize("page", [16, 32, 64])  # 8 / 4 / 2 pages a KV block
def test_paged_matches_decode_ref_ragged(H, KV, page):
    B, S, d = 5, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = rand(ks[0], (B, H, d))
    k = rand(ks[1], (B, S, KV, d))
    v = rand(ks[2], (B, S, KV, d))
    # ragged incl. extremes; 37 ends mid-block, 133 in the second 128-key
    # block's first page
    lengths = jnp.array([1, 37, page, 133, S], jnp.int32)
    pool_k, tables = dense_to_pool(k, page)
    pool_v, _ = dense_to_pool(v, page)
    expect = ref.decode_attention_ref(
        q.reshape(B, KV, H // KV, d), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lengths,
    ).reshape(B, H, d)
    for kwargs in (dict(), dict(interpret=True)):
        got = ops.paged_attention_rows(q, pool_k, pool_v, lengths, tables, **kwargs)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
        )


@pytest.mark.parametrize("window", [None, 48, 140])  # both straddle key 128
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_paged_window_softcap(window, softcap):
    B, H, KV, S, d, page = 3, 4, 2, 256, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = rand(ks[0], (B, H, d))
    k = rand(ks[1], (B, S, KV, d))
    v = rand(ks[2], (B, S, KV, d))
    lengths = jnp.array([13, 130, 256], jnp.int32)
    pool_k, tables = dense_to_pool(k, page)
    pool_v, _ = dense_to_pool(v, page)
    expect = ref.decode_attention_ref(
        q.reshape(B, KV, H // KV, d), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lengths, window=window, softcap=softcap,
    ).reshape(B, H, d)
    for kwargs in (dict(), dict(interpret=True)):
        got = ops.paged_attention_rows(
            q, pool_k, pool_v, lengths, tables, window=window, softcap=softcap, **kwargs
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
        )


def test_paged_block_table_permutation():
    """Physically shuffled pages + matching tables == contiguous layout:
    the block-table indirection is what the kernel actually follows."""
    B, H, KV, S, d, page = 3, 8, 2, 256, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = rand(ks[0], (B, H, d))
    k = rand(ks[1], (B, S, KV, d))
    v = rand(ks[2], (B, S, KV, d))
    lengths = jnp.array([25, 160, 256], jnp.int32)
    pool_k, tables = dense_to_pool(k, page)
    pool_v, _ = dense_to_pool(v, page)
    base = ops.paged_attention_rows(q, pool_k, pool_v, lengths, tables)

    perm = np.random.default_rng(0).permutation(pool_k.shape[0])
    inv = np.argsort(perm)
    pool_k_p = pool_k[perm]
    pool_v_p = pool_v[perm]
    tables_p = jnp.asarray(inv[np.asarray(tables)])  # logical order preserved
    for kwargs in (dict(), dict(interpret=True)):
        got = ops.paged_attention_rows(
            q, pool_k_p, pool_v_p, lengths, tables_p, **kwargs
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(base), rtol=2e-5, atol=2e-5
        )


@pytest.mark.parametrize("page", [16, 64])
def test_paged_tail_entries_never_read(page):
    """Table entries past ceil(length/page) may point anywhere valid —
    corrupting those pages must not change the output, also where they
    share a KV block with live pages."""
    B, H, KV, S, d = 2, 4, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = rand(ks[0], (B, H, d))
    k = rand(ks[1], (B, S, KV, d))
    v = rand(ks[2], (B, S, KV, d))
    # page 64: 1 / 2 live pages of 4; page 16: 3 / 5 of 16, all in block 0
    lengths = jnp.array([40, 70], jnp.int32)
    pool_k, tables = dense_to_pool(k, page)
    pool_v, _ = dense_to_pool(v, page)
    out1 = ops.paged_attention_rows(q, pool_k, pool_v, lengths, tables, interpret=True)
    # corrupt every page, then rebuild only the live ones
    live = {int(tables[b, j]) for b in range(B) for j in range(-(-int(lengths[b]) // page))}
    mask = np.zeros((pool_k.shape[0], 1, 1, 1), np.float32)
    mask[sorted(live)] = 1.0
    out2 = ops.paged_attention_rows(
        q, pool_k * mask + 999.0 * (1 - mask), pool_v * mask - 999.0 * (1 - mask),
        lengths, tables, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6, atol=1e-6)


def test_tokens_touched_accounting():
    """Ragged reads strictly fewer tokens than the padded dense gather at
    mixed lengths, and exactly ceil(len/page)*page per row."""
    lengths, page, s_max = [1, 37, 64, 100], 32, 1024
    touched = tokens_touched(lengths, page)
    assert touched == 32 + 64 + 64 + 128
    assert touched < len(lengths) * s_max


@pytest.mark.parametrize("nb", [8, 64, 1024, 2048])
def test_mixed_grid_block_of_pages(nb):
    """One grid step per 128-key block: at page 16 and nb >= 8 the kernel
    takes 8x fewer grid steps than one per page (8,192 instead of 65,536
    per call at the qwen3-32b-l8 cell's S 8, KV 8, nb 1024); larger pages
    take fewer pages a block, and the block never exceeds the table."""
    S, KV = 8, 8
    assert kv_block_pages(nb, 16) == 8
    assert np.prod(mixed_grid(S, KV, nb, 16)) * 8 == S * KV * nb
    assert mixed_grid(S, KV, nb, 32) == (S, KV, nb // 4)
    assert mixed_grid(S, KV, nb, 256) == (S, KV, nb)
    assert mixed_grid(S, KV, 4, 16) == (S, KV, 1)  # capped at nb
    assert mixed_grid(S, KV, 12, 16) == (S, KV, 2)  # a partial last block
    if nb == 1024:
        assert np.prod(mixed_grid(S, KV, nb, 16)) == 8192


@pytest.mark.parametrize("window", [None, 1, 5, 20])
def test_mixed_block_copies_start_each_running_block_once(window):
    """The kernel's block skipping and double-buffer schedule, brute-forced:
    a block runs exactly when some query of the segment attends one of its
    keys, and each running block's copies are started exactly once (a
    second start would leave its semaphore signalled into the next wait),
    none for a skipped block."""
    blk, nbb = 8, 6
    for kv_len in range(nbb * blk + 1):
        for q_len in {0, 1, 3, 8, kv_len}:
            if q_len > kv_len:
                continue
            runs = lambda b: _block_runs(b, blk=blk, kv_len=kv_len,
                                         q_len=q_len, window=window)
            starts = [0] * (nbb + 1)
            for ib in range(nbb):
                own, nxt = _block_copies(ib, nbb, runs)
                starts[ib] += bool(own)
                starts[ib + 1] += bool(nxt)
            for b in range(nbb):
                attended = any(
                    k <= q_pos and (window is None or k > q_pos - window)
                    for q_pos in range(kv_len - q_len, kv_len)
                    for k in range(b * blk, (b + 1) * blk))
                assert bool(runs(b)) == attended, (kv_len, q_len, b)
                assert starts[b] == attended, (kv_len, q_len, b)
            assert starts[nbb] == 0


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

MAX_LEN = 64


def _serial_reference(model, params, req):
    from repro.serving import sampling

    cache = model.init_cache(1, MAX_LEN, jnp.float32)
    batch = {"tokens": jnp.asarray(np.asarray(req.prompt, np.int32)[None])}
    logits, cache = jax.jit(model.prefill)(params, batch, cache, jnp.int32(0))
    out = [int(sampling.greedy(logits[0]))]
    pos = len(req.prompt)
    decode = jax.jit(model.decode_step)
    while len(out) < req.max_new_tokens:
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, cache = decode(params, tok, cache, jnp.int32(pos))
        out.append(int(sampling.greedy(logits[0])))
        pos += 1
    return out


def _requests(cfg, seed, n=3):
    rng = jax.random.PRNGKey(seed)
    lens = [5, 17, 9][:n]
    outs = [6, 4, 8][:n]
    return [
        Request(
            rid=i,
            prompt=np.asarray(
                jax.random.randint(jax.random.fold_in(rng, i), (lens[i],), 0, cfg.vocab_size)
            ).tolist(),
            max_new_tokens=outs[i],
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("arch", ["llama3.1-8b", "gemma2-2b"])
def test_engine_paged_token_identical_to_dense_and_serial(arch):
    """The ragged paged default must not change a single token vs the dense
    gather or the serial reference (gemma covers window + softcap)."""
    cfg = reduce_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(cfg, 42)
    expected = {r.rid: _serial_reference(model, params, r) for r in reqs}

    sched = dict(chunk_size=8, max_decode_batch=3, prefetch_buffer_bytes=1 << 20,
                 max_concurrent_prefills=2, kv_block_size=4)
    outs = {}
    for kernel in ("paged", "dense"):
        eng = Engine(model, params, SchedulerConfig(**sched), max_len=MAX_LEN,
                     attn_kernel=kernel)
        assert eng.attn_kernel == kernel
        for r in reqs:
            eng.submit(Request(rid=r.rid, prompt=list(r.prompt),
                               max_new_tokens=r.max_new_tokens))
        eng.run(max_steps=300)
        outs[kernel] = {r.rid: eng.scheduler.requests[r.rid].output for r in reqs}

    for r in reqs:
        assert outs["paged"][r.rid] == expected[r.rid]
        assert outs["paged"][r.rid] == outs["dense"][r.rid]


def test_engine_block_mirror_lifecycle():
    """The device block-table mirror tracks the allocator across admission,
    swap preemption, restore, and completion: live slots carry their table's
    *real physical page ids* (never a slot-derived identity map), everything
    else points at the scratch page."""
    cfg = reduce_config(get_config("llama3.1-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(
        model, params,
        SchedulerConfig(chunk_size=16, max_decode_batch=3,
                        prefetch_buffer_bytes=1 << 20, max_concurrent_prefills=2,
                        kv_capacity_tokens=30, preemption="swap", kv_block_size=4),
        max_len=MAX_LEN,
    )
    assert eng.attn_kernel == "paged"
    # default pool = the dense layout's capacity, bounded
    assert eng.scheduler.mem.allocator.num_blocks == eng.num_pool_pages
    assert eng.num_pool_pages == eng.n_slots * eng.pages_per_slot
    for r in _requests(cfg, 44):
        eng.submit(r)

    pps = eng.pages_per_slot
    scratch = eng._scratch_page
    saw_scratched_free = False
    saw_nonidentity = False
    while eng.scheduler.has_work and eng.steps_run < 300:
        sch = eng.scheduler
        plan = sch.next_step(now=float(eng.steps_run))
        if plan is None:
            break
        eng._apply_swaps(plan)
        eng._run_packed(plan)  # syncs the mirror before compute
        active_slots = set(sch.active.keys())
        for slot in range(eng.n_slots):
            row = eng.block_mirror[slot]
            if slot not in active_slots:
                assert (row == scratch).all(), f"dead slot {slot} not scratched"
                saw_scratched_free = True
            else:
                rid = sch.active[slot].rid
                table = sch.mem.allocator.tables.get(rid)
                if table is not None:
                    n = min(pps, table.num_blocks)
                    # the mirror is the allocator's table, verbatim
                    assert list(row[:n]) == table.blocks[:n]
                    assert (row[n:] == scratch).all()
                    if list(row[:n]) != [slot * pps + j for j in range(n)]:
                        saw_nonidentity = True
        # the scratch slot's whole row is the single scratch page (padding
        # rows write their garbage K/V there)
        assert (eng.block_mirror[eng.n_slots] == scratch).all()
        sch.complete_step(plan, now=float(eng.steps_run))
        eng.steps_run += 1

    assert eng.scheduler.stats.swap_outs > 0, "swap pressure never triggered"
    assert saw_scratched_free
    assert saw_nonidentity, "allocator ids never diverged from the slot map"
    for r in eng.scheduler.requests.values():
        assert len(r.output) == r.max_new_tokens


# ---------------------------------------------------------------------------
# unified mixed-batch kernel: parity vs the old per-token ragged path
# ---------------------------------------------------------------------------


def _mixed_case(rng, *, KV, G, page, segs, pad, window, softcap, nb=None):
    """A mixed batch of decode rows, prefill chunks and dead zero-width
    segments (``segs``: (q_len, kv_len) each) over a shuffled page pool
    whose dead pages are corrupted. ``nb`` defaults to one column past the
    longest table. Returns everything both attention paths need plus the
    per-token expansion the OLD per-row path consumes."""
    d = 32
    n_seg = len(segs)
    live_per_seg = [-(-kv // page) for _, kv in segs]
    if nb is None:
        nb = max(max(live_per_seg, default=1) + 1, 2)
    P = sum(live_per_seg) + 4  # + dead garbage pages
    page_ids = rng.permutation(P)
    dead = list(page_ids[sum(live_per_seg):])
    tables = np.asarray(rng.choice(dead, size=(n_seg, nb)), np.int32)
    off = 0
    for s, n_live in enumerate(live_per_seg):
        tables[s, :n_live] = page_ids[off:off + n_live]
        off += n_live

    pool_k = rng.standard_normal((P, KV, page, d)).astype(np.float32)
    pool_v = rng.standard_normal((P, KV, page, d)).astype(np.float32)
    pool_k[dead] = 999.0  # corrupted: any read would wreck the softmax
    pool_v[dead] = -999.0

    n_real = sum(q for q, _ in segs)
    N = n_real + pad
    q = rng.standard_normal((N, KV * G, d)).astype(np.float32)
    cu = np.zeros((n_seg + 1,), np.int32)
    cu[1:] = np.cumsum([q_len for q_len, _ in segs])
    kv_lens = np.asarray([kv for _, kv in segs], np.int32)
    # per-token expansion for the old per-row path
    row_len, row_tab = [], []
    for s, (q_len, kv_len) in enumerate(segs):
        for j in range(q_len):
            row_len.append(kv_len - q_len + j + 1)
            row_tab.append(tables[s])
    qb = 1
    while qb < max((q for q, _ in segs), default=1):
        qb *= 2
    return dict(q=q, pool_k=pool_k, pool_v=pool_v, cu=cu, kv_lens=kv_lens,
                tables=tables, qb=qb, window=window, softcap=softcap,
                n_real=n_real, row_len=np.asarray(row_len, np.int32),
                row_tab=np.asarray(row_tab, np.int32).reshape(len(row_tab), nb))


def _draw_mixed_case(data, st):
    """Random mixed batch: contexts up to 300 keys, so KV blocks of 128 keys
    end mid-table, and ``nb`` either tight or the engine's pow2 bucket."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    page = data.draw(st.sampled_from([8, 16, 32]))
    segs = []  # (q_len, kv_len)
    for _ in range(data.draw(st.integers(1, 4))):
        kind = data.draw(st.sampled_from(["decode", "chunk", "chunk", "dead"]))
        if kind == "decode":
            segs.append((1, data.draw(st.integers(1, 300))))
        elif kind == "chunk":
            q_len = data.draw(st.integers(2, 6))
            segs.append((q_len, data.draw(st.integers(q_len, 300))))
        else:
            segs.append((0, 0))
    nb = None
    if data.draw(st.booleans()):
        nb = 8
        while nb * page < max(kv for _, kv in segs):
            nb *= 2
    return _mixed_case(
        rng, KV=data.draw(st.sampled_from([1, 2])),
        G=data.draw(st.sampled_from([1, 2, 4, 6, 8])), page=page, segs=segs,
        pad=data.draw(st.integers(0, 3)),
        window=data.draw(st.sampled_from([None, 5, 16, 130])),
        softcap=data.draw(st.sampled_from([None, 20.0])), nb=nb)


def _check_mixed_matches_per_token_path(c, modes=(dict(use_kernel=False),
                                                 dict(interpret=True))):
    if c["n_real"] == 0:
        return  # all segments dead: nothing to compare
    expect = ops.paged_attention_rows(
        jnp.asarray(c["q"][:c["n_real"]]), jnp.asarray(c["pool_k"]),
        jnp.asarray(c["pool_v"]), jnp.asarray(c["row_len"]),
        jnp.asarray(c["row_tab"]), window=c["window"], softcap=c["softcap"],
    )
    for kwargs in modes:
        got = ops.mixed_attention_rows(
            jnp.asarray(c["q"]), jnp.asarray(c["pool_k"]),
            jnp.asarray(c["pool_v"]), jnp.asarray(c["cu"]),
            jnp.asarray(c["kv_lens"]), jnp.asarray(c["tables"]),
            qb=c["qb"], window=c["window"], softcap=c["softcap"], **kwargs,
        )
        np.testing.assert_allclose(
            np.asarray(got[:c["n_real"]]), np.asarray(expect),
            rtol=2e-5, atol=2e-5,
        )
        assert np.all(np.isfinite(np.asarray(got)))


@settings(deadline=None, max_examples=15)
@given(data=st.data())
def test_mixed_matches_per_token_path(data):
    """Property parity: the unified mixed-batch attention (jnp oracle AND
    Pallas kernel in interpret mode) equals the OLD per-token ragged path on
    random decode/prefill mixes — shuffled non-contiguous tables, GQA,
    window, softcap, zero-width segments, and corrupted dead pages that must
    never be read."""
    _check_mixed_matches_per_token_path(_draw_mixed_case(data, st))


# the kernel's KV-block edges, pinned: (KV, G, page, nb, segs, pad, window)
BLOCK_EDGE_CASES = {
    # one block covers the whole 8-column table; 128 ends on its edge
    "nb_is_one_block": (1, 8, 16, 8, [(1, 100), (6, 128), (0, 0)], 2, None),
    # 4-page blocks; 200 ends mid-block, 129 in block 1's first page
    "page32_block_ends": (2, 6, 32, 8, [(1, 200), (4, 129), (0, 0)], 1, None),
    # the chunk's window starts in block 2 (blocks 0-1 skipped, block 2
    # fetches itself); the decode's window straddles key 128
    "window_over_block_edge": (1, 8, 16, 32, [(3, 300), (1, 140)], 0, 40),
    # zero-width padding segments around live ones, and padding rows
    "padding_segments": (2, 6, 16, 16, [(0, 0), (5, 133), (0, 0), (1, 17), (0, 0)],
                         3, None),
}


@pytest.mark.parametrize("case", sorted(BLOCK_EDGE_CASES))
def test_mixed_block_edges_match_per_token_path(case):
    """The property above at the KV-block edges of the block-per-step grid,
    also under the TPU interpreter, which models the kernel's page DMAs and
    semaphores, fills never-written VMEM with NaN and raises on a read out
    of a buffer's bounds."""
    KV, G, page, nb, segs, pad, window = BLOCK_EDGE_CASES[case]
    c = _mixed_case(
        np.random.default_rng(0), KV=KV, G=G, page=page, segs=segs, pad=pad,
        window=window, softcap=None, nb=nb)
    tpu_interpret = dict(interpret=pltpu.InterpretParams())
    _check_mixed_matches_per_token_path(
        c, modes=(dict(use_kernel=False), dict(interpret=True), tpu_interpret))
    # table entries past each segment's pages, even those sharing a block
    # with live pages, are never fetched: out of the pool, they would raise
    dead = np.arange(nb)[None, :] >= -(-c["kv_lens"][:, None] // page)
    c["tables"] = np.where(dead, c["pool_k"].shape[0] + 1000, c["tables"])
    _check_mixed_matches_per_token_path(c, modes=(tpu_interpret,))


# the latent (MLA) kernel: (page, nb, segs (q_len, kv_len), padding rows)
MLA_CASES = {
    # decodes beside a chunk; 200 ends mid-block, the chunk starts block 1
    "decodes_and_chunk": (16, 16, [(1, 200), (1, 17), (6, 133), (0, 0)], 2),
    # 128 ends on a block edge, 129 one key into the next
    "block_edges": (16, 16, [(1, 128), (4, 129), (1, 1)], 0),
    # 4-page blocks of 32-token pages
    "page32": (32, 8, [(3, 70), (1, 250)], 1),
}


@pytest.mark.parametrize("case", sorted(MLA_CASES))
def test_mla_kernel_matches_oracle(case):
    """``mixed_paged_mla_attention`` (interpret mode, and the TPU
    interpreter that models its page DMAs) and its jnp oracle equal a
    per-row float64 computation on mixed decode and chunk segments: scores
    over the latent and rope keys, softmax over the row's own keys, the
    latent values. Pools carry their keys in the leading lanes of 128, as
    the engine's do, and table entries past a segment's pages are never
    fetched."""
    from repro.kernels.paged_attention import mixed_paged_mla_attention

    page, nb, segs, pad = MLA_CASES[case]
    rng = np.random.default_rng(1)
    H, L, R, W, scale = 4, 128, 64, 128, 0.07
    P = sum(-(-kv // page) for _, kv in segs) + 3
    perm = rng.permutation(P)
    tables = np.zeros((len(segs), nb), np.int32)
    used = 0
    for s, (_, kv) in enumerate(segs):
        n = -(-kv // page)
        tables[s, :n] = perm[used:used + n]
        used += n
    q_lens = [q for q, _ in segs]
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    N = int(cu[-1]) + pad
    ql = rng.normal(size=(N, H, L)).astype(np.float32)
    qr = rng.normal(size=(N, H, R)).astype(np.float32)
    c = rng.normal(size=(P, page, L)).astype(np.float32)
    kr = np.zeros((P, page, W), np.float32)
    kr[..., :R] = rng.normal(size=(P, page, R))
    kv_lens = np.asarray([kv for _, kv in segs], np.int32)

    want = np.zeros((int(cu[-1]), H, L))
    for s, (q_len, kv) in enumerate(segs):
        keys = tables[s, :-(-kv // page)] if kv else []
        ck = c[keys].reshape(-1, L).astype(np.float64)
        rk = kr[keys].reshape(-1, W)[:, :R].astype(np.float64)
        for j in range(q_len):
            n = kv - q_len + j + 1
            sc = (ql[cu[s] + j] @ ck[:n].T + qr[cu[s] + j] @ rk[:n].T) * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want[cu[s] + j] = (p / p.sum(-1, keepdims=True)) @ ck[:n]

    args = lambda t: (jnp.asarray(ql), jnp.asarray(qr), jnp.asarray(c), jnp.asarray(kr),
                      jnp.asarray(cu), jnp.asarray(kv_lens), jnp.asarray(t))
    got = [ref.mixed_mla_attention_ref(*args(tables), scale=scale)]
    dead = np.arange(nb)[None, :] >= -(-kv_lens[:, None] // page)
    poisoned = np.where(dead, P + 1000, tables)  # out of the pool: a read raises
    for interp in (True, pltpu.InterpretParams()):
        got.append(mixed_paged_mla_attention(*args(poisoned), qb=8, scale=scale,
                                             interpret=interp))
    for g in got:
        assert g.shape == (N, H, L) and np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g)[:int(cu[-1])], want, rtol=2e-5, atol=2e-5)


def test_engine_mixed_swap_oversubscribed_token_identity():
    """Unified path == dense debug fallback == serial reference under swap
    preemption on a genuinely over-subscribed 16-page pool (total demand 21
    pages): page round-trips through the host tier must not change a token."""
    cfg = reduce_config(get_config("llama3.1-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(9)
    # long decode phases so all three full contexts coexist: 40/36/32 tokens
    # -> 10+9+8 = 27 pages of demand against a 16-page pool
    lens, outs = [24, 20, 16], [16, 16, 16]
    reqs = [
        Request(rid=i, prompt=np.asarray(
            jax.random.randint(jax.random.fold_in(rng, i), (lens[i],), 0,
                               cfg.vocab_size)).tolist(),
            max_new_tokens=outs[i])
        for i in range(3)
    ]
    expected = {r.rid: _serial_reference(model, params, r) for r in reqs}

    sched = dict(chunk_size=8, max_decode_batch=3,
                 prefetch_buffer_bytes=1 << 20, max_concurrent_prefills=2,
                 kv_block_size=4, num_kv_blocks=16, preemption="swap")
    outs_by_kernel = {}
    for kernel in ("paged", "dense"):
        eng = Engine(model, params, SchedulerConfig(**sched), max_len=MAX_LEN,
                     attn_kernel=kernel)
        for r in reqs:
            eng.submit(Request(rid=r.rid, prompt=list(r.prompt),
                               max_new_tokens=r.max_new_tokens))
        eng.run(max_steps=400)
        if kernel == "paged":
            assert eng.scheduler.stats.swap_outs > 0, "pool never thrashed"
        outs_by_kernel[kernel] = {
            r.rid: eng.scheduler.requests[r.rid].output for r in reqs}

    for r in reqs:
        assert outs_by_kernel["paged"][r.rid] == expected[r.rid]
        assert outs_by_kernel["paged"][r.rid] == outs_by_kernel["dense"][r.rid]


def test_packed_jit_cache_bounded():
    """Recompile regression: pow2 bucketing of (nb, n_segments, q_block)
    keeps the packed jit cache from growing with workload shape — many steps
    of shifting decode/prefill mixes compile only a handful of variants."""
    cfg = reduce_config(get_config("llama3.1-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 SchedulerConfig(chunk_size=8, max_decode_batch=3,
                                 prefetch_buffer_bytes=1 << 20,
                                 max_concurrent_prefills=2, kv_block_size=4),
                 max_len=MAX_LEN)
    assert eng.attn_kernel == "paged"
    rng = jax.random.PRNGKey(3)
    lens = [3, 5, 7, 9, 11, 14, 17, 21]  # varied -> varied chunk tails
    for i, n in enumerate(lens):
        eng.submit(Request(rid=i, prompt=np.asarray(
            jax.random.randint(jax.random.fold_in(rng, i), (n,), 0,
                               cfg.vocab_size)).tolist(),
            max_new_tokens=4 + (i % 3)))
    eng.run(max_steps=400)
    for i in range(len(lens)):
        req = eng.scheduler.requests[i]
        assert len(req.output) == req.max_new_tokens
    assert eng.steps_run > 8
    # compiled variants: one per (qb bucket) at fixed (N, nb, sb) here —
    # far fewer than steps, and bounded regardless of how long we run
    assert eng._packed._cache_size() <= 6
    assert eng._packed._cache_size() < eng.steps_run


# ---------------------------------------------------------------------------
# pools stacked over layers: the kernels read a layer by index and the step
# writes into its layer in place
# ---------------------------------------------------------------------------


def _stack(rng, pool, layer, n_layers=3):
    """``pool`` as layer ``layer`` of an (n_layers, ...) stack whose other
    layers hold different random contents."""
    out = rng.standard_normal((n_layers,) + pool.shape).astype(pool.dtype)
    out[layer] = pool
    return out


@pytest.mark.parametrize("interp", ["interpret", "tpu_interpreter"])
def test_mixed_kernel_reads_one_layer_of_a_stack(interp):
    """Layer 2 of a 3-layer stack whose layers 0-1 hold other keys and
    values: the kernel matches the oracle on that layer's pool, and the
    unstacked form on the same pool gives the same result bit for bit."""
    from repro.kernels.paged_attention import mixed_paged_attention, ragged_mixed_attention

    KV, G, page, nb, segs, pad, window = BLOCK_EDGE_CASES["padding_segments"]
    rng = np.random.default_rng(5)
    c = _mixed_case(rng, KV=KV, G=G, page=page, segs=segs, pad=pad, window=window,
                    softcap=None, nb=nb)
    q = jnp.asarray(c["q"]).reshape(-1, KV, G, c["q"].shape[-1])
    sk, sv = (jnp.asarray(_stack(rng, c[n], 2)) for n in ("pool_k", "pool_v"))
    meta = (jnp.asarray(c["cu"]), jnp.asarray(c["kv_lens"]), jnp.asarray(c["tables"]))
    mode = True if interp == "interpret" else pltpu.InterpretParams()
    got = mixed_paged_attention(q, sk, sv, *meta, jnp.int32(2), qb=c["qb"], interpret=mode)
    one = mixed_paged_attention(q, sk[2], sv[2], *meta, qb=c["qb"], interpret=mode)
    want = ref.mixed_attention_ref(q, jnp.asarray(c["pool_k"]), jnp.asarray(c["pool_v"]),
                                   *meta)
    oracle = ragged_mixed_attention(q, sk, sv, *meta, jnp.int32(2), qb=c["qb"])
    n = c["n_real"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    np.testing.assert_array_equal(np.asarray(oracle), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("interp", ["interpret", "tpu_interpreter"])
def test_mla_kernel_reads_one_layer_of_a_stack(interp):
    """The latent kernel on layer 2 of 3-layer latent and rope stacks: the
    oracle on that layer's pools, and the unstacked form bit for bit."""
    from repro.kernels.paged_attention import (
        mixed_paged_mla_attention, ragged_mixed_mla_attention)

    page, nb, segs, pad = MLA_CASES["decodes_and_chunk"]
    rng = np.random.default_rng(6)
    H, L, R, W, scale = 4, 128, 64, 128, 0.07
    P = sum(-(-kv // page) for _, kv in segs) + 3
    tables = rng.integers(0, P, (len(segs), nb)).astype(np.int32)
    cu = np.concatenate([[0], np.cumsum([q for q, _ in segs])]).astype(np.int32)
    N = int(cu[-1]) + pad
    ql = jnp.asarray(rng.normal(size=(N, H, L)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(N, H, R)), jnp.float32)
    c = rng.normal(size=(P, page, L)).astype(np.float32)
    kr = np.zeros((P, page, W), np.float32)
    kr[..., :R] = rng.normal(size=(P, page, R))
    sc, sr = jnp.asarray(_stack(rng, c, 2)), jnp.asarray(_stack(rng, kr, 2))
    meta = (jnp.asarray(cu), jnp.asarray([kv for _, kv in segs], jnp.int32),
            jnp.asarray(tables))
    mode = True if interp == "interpret" else pltpu.InterpretParams()
    got = mixed_paged_mla_attention(ql, qr, sc, sr, *meta, jnp.int32(2), qb=8,
                                    scale=scale, interpret=mode)
    one = mixed_paged_mla_attention(ql, qr, sc[2], sr[2], *meta, qb=8, scale=scale,
                                    interpret=mode)
    want = ref.mixed_mla_attention_ref(ql, qr, jnp.asarray(c), jnp.asarray(kr), *meta,
                                       scale=scale)
    oracle = ragged_mixed_mla_attention(ql, qr, sc, sr, *meta, jnp.int32(2), qb=8,
                                        scale=scale)
    n = int(cu[-1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    np.testing.assert_array_equal(np.asarray(oracle), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["gqa_pages", "latent_rows"])
def test_scatter_into_stacked_pool_equals_row_scatter(kind):
    """``PagedView.scatter`` into layer 1 of a 3-layer stack equals writing
    the rows one by one: a decode row, a chunk whose rows share pages and
    cross a page boundary, and padding rows (identical, as the engine's
    are) onto the scratch page. The other layers stay bit-identical, and
    the unstacked form writes the same layer."""
    from repro.core.packed_step import PagedView

    rng = np.random.default_rng(7)
    page, layer, KV, d, W = 4, 1, 2, 8, 16
    P = 12
    scratch = P - 1
    # slot 0 decodes at position 9, slot 1 prefills positions 2..7 (pages 0,
    # 1 of its table: a boundary at 4), slot 2 is the scratch slot
    tables = np.asarray([[3, 7, 5], [8, 0, 9], [scratch] * 3], np.int32)
    slots = np.asarray([0, 1, 1, 1, 1, 1, 1, 2, 2, 2], np.int32)
    positions = np.asarray([9, 2, 3, 4, 5, 6, 7, 0, 0, 0], np.int32)
    N = len(slots)
    shape = (P, KV, page, d) if kind == "gqa_pages" else (P, page, W)
    pool = rng.standard_normal((3,) + shape).astype(np.float32)
    values = rng.standard_normal((N, KV, d) if kind == "gqa_pages" else (N, W - 5))
    values = values.astype(np.float32)
    values[slots == 2] = values[-1]  # padding rows: one token at one position
    want = pool.copy()
    for n in range(N):
        pg, off = tables[slots[n], positions[n] // page], positions[n] % page
        if kind == "gqa_pages":
            want[layer, pg, :, off] = values[n]
        else:
            want[layer, pg, off] = np.pad(values[n], (0, W - values.shape[1]))
    view = PagedView(jnp.asarray(tables), page)
    args = (jnp.asarray(slots), jnp.asarray(positions), jnp.asarray(values))
    got = np.asarray(jax.jit(view.scatter)(jnp.asarray(pool), *args, jnp.int32(layer)))
    np.testing.assert_array_equal(got, want)
    for other in (0, 2):
        np.testing.assert_array_equal(got[other], pool[other])
    one = np.asarray(jax.jit(view.scatter)(jnp.asarray(pool[layer]), *args))
    np.testing.assert_array_equal(one, want[layer])
