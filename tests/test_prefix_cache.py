"""Radix prefix cache: copy-on-write KV sharing over the physical page pool.

Covers the PR's acceptance statement: refcount invariants under random
fork/free/evict/swap churn (no leaks, no double-free, shared pages never
scribbled), token-identity of greedy outputs with the cache on vs off —
including under swap preemption and an over-subscribed pool — and
eviction-under-pressure / admission-watermark behaviour.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, st

from repro.configs import get_config, reduce_config
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.memory import KVMemoryManager, OutOfBlocks, hbm_kv_pool_blocks
from repro.models import build_model
from repro.serving import sampling
from repro.serving.engine import Engine
from repro.serving.metrics import summarize
from repro.serving.request import Request, State
from repro.serving.workload import multi_turn_requests, shared_prefix_requests

CFG = get_config("llama3.1-8b")
MAX_LEN = 64


# ---------------------------------------------------------------------------
# radix trie: match / insert / evict mechanics
# ---------------------------------------------------------------------------


def _mem(bs=4, pool=None, cache_blocks=None):
    return KVMemoryManager(CFG, block_size=bs, num_blocks=pool,
                           enable_prefix_cache=True,
                           prefix_cache_blocks=cache_blocks)


def test_radix_match_insert_basic():
    mem = _mem(bs=4)
    toks = list(range(100, 112))  # 3 full blocks
    mem.on_prefill(0, len(toks))
    assert mem.insert_prefix(0, toks) == 3
    # a prompt sharing the first 8 tokens matches exactly 2 blocks
    probe = toks[:8] + [7, 7, 7, 7]
    matched = mem.match_prefix(1, probe, max_tokens=len(probe) - 1)
    assert matched == 8
    t0, t1 = mem.allocator.tables[0], mem.allocator.tables[1]
    assert t1.blocks == t0.blocks[:2]  # physical pages shared, not copied
    assert all(mem.allocator.ref_count[b] >= 2 for b in t1.blocks)
    # suffix prefill grows PRIVATE tail blocks (shared pages never scribbled)
    before = list(t1.blocks)
    mem.on_prefill(1, 4)
    new = [b for b in t1.blocks if b not in before]
    assert new and all(mem.allocator.ref_count[b] == 1 for b in new)


def test_match_leaves_last_token_uncached():
    """A fully cached prompt still computes its final token: the match is
    capped so the finishing chunk emits the first output logits. With
    mid-block resume the cap lands INSIDE the second block — the match
    fast-forwards to 7 of 8 tokens via a copy-on-write tail page."""
    mem = _mem(bs=4)
    toks = list(range(200, 208))  # exactly 2 blocks
    mem.on_prefill(0, len(toks))
    mem.insert_prefix(0, toks)
    matched = mem.match_prefix(1, list(toks), max_tokens=len(toks) - 1)
    assert matched == 7  # 1 full block adopted + 3-token partial tail
    t0, t1 = mem.allocator.tables[0], mem.allocator.tables[1]
    # first block shared, tail block a PRIVATE copy (never the cached page)
    assert t1.blocks[0] == t0.blocks[0]
    assert t1.blocks[1] != t0.blocks[1]
    assert mem.allocator.ref_count[t1.blocks[1]] == 1
    # the engine drains one copy intent: cached tail -> private page, 3 toks
    assert mem.drain_prefix_copies() == [(1, t0.blocks[1], t1.blocks[1], 3)]
    assert mem.drain_prefix_copies() == []  # drained exactly once


def test_insert_keeps_existing_nodes():
    """Re-inserting an already-cached prefix adopts nothing new; the second
    request keeps its private duplicate and the cache keys stay unique."""
    mem = _mem(bs=4)
    toks = list(range(50, 58))
    mem.on_prefill(0, 8)
    assert mem.insert_prefix(0, toks) == 2
    mem.on_prefill(1, 8)  # same tokens, computed privately (no match call)
    assert mem.insert_prefix(1, toks) == 0
    assert mem.prefix.cached_blocks == 2


def test_eviction_order_priority_then_lru():
    mem = _mem(bs=4)
    mem.on_prefill(0, 4)
    mem.insert_prefix(0, [1, 1, 1, 1], step=5, priority=0)
    mem.on_prefill(1, 4)
    mem.insert_prefix(1, [2, 2, 2, 2], step=1, priority=3)
    mem.on_prefill(2, 4)
    mem.insert_prefix(2, [3, 3, 3, 3], step=9, priority=0)
    for r in range(3):
        mem.free(r)
    # lowest priority first, then least recently accessed: rid0's block
    # (prio 0, step 5) goes before rid2's (prio 0, step 9); rid1 last
    b0 = mem.prefix.match([1, 1, 1, 1])  # refreshes nothing (step 0 < 5)
    assert b0
    assert mem.prefix.evict(1) == 1
    assert not mem.prefix.match([1, 1, 1, 1])
    assert mem.prefix.match([3, 3, 3, 3])
    assert mem.prefix.match([2, 2, 2, 2])
    assert mem.prefix.evict(2) == 2
    assert mem.prefix.cached_blocks == 0


def test_referenced_blocks_never_evicted():
    mem = _mem(bs=4, pool=8)
    toks = list(range(60, 68))
    mem.on_prefill(0, 8)
    mem.insert_prefix(0, toks)
    matched = mem.match_prefix(1, toks + [9, 9, 9, 9])
    assert matched == 8
    mem.free(0)  # rid1 + cache still reference the pages
    assert mem.prefix.evict(10) == 0  # nothing reclaimable
    assert mem.allocator.tables[1].num_tokens == 8


def test_grow_evicts_cache_under_pressure():
    """OutOfBlocks pressure reclaims unreferenced cache leaves before growth
    fails — and genuinely exhausted pools still raise."""
    mem = _mem(bs=4, pool=8)
    toks = list(range(300, 316))  # 4 blocks
    mem.on_prefill(0, 16)
    mem.insert_prefix(0, toks)
    mem.free(0)
    assert mem.allocator.free_blocks == 4
    assert mem.prefix.reclaimable_blocks() == 4
    assert mem.effective_free_blocks() == 8
    mem.on_prefill(1, 28)  # 7 blocks: needs 3 evictions
    assert mem.prefix.cached_blocks == 1
    assert mem.allocator.tables[1].num_tokens == 28
    with pytest.raises(OutOfBlocks):
        mem.on_prefill(2, 8)  # 1 free + 1 cached-but... actually 0 free
    assert mem.tokens_of(2) == 0  # transactional failure


def test_prefix_cache_blocks_cap():
    mem = _mem(bs=4, cache_blocks=2)
    mem.on_prefill(0, 16)
    assert mem.insert_prefix(0, list(range(400, 416))) == 2
    assert mem.prefix.cached_blocks == 2  # capped; eldest stay while used


# ---------------------------------------------------------------------------
# property: refcount invariants under random churn
# ---------------------------------------------------------------------------


def _audit(mem):
    """Every block's refcount equals tables + cache nodes + swap-kept refs;
    the free list is disjoint from live blocks."""
    alloc = mem.allocator
    expect = {}
    for t in alloc.tables.values():
        for b in t.blocks:
            expect[b] = expect.get(b, 0) + 1
    for b in mem.prefix.block_ids():
        expect[b] = expect.get(b, 0) + 1
    for rec in mem.swapped.values():
        for b in rec.record.kept_blocks:
            expect[b] = expect.get(b, 0) + 1
    assert expect == alloc.ref_count, (
        f"refcount drift: expected {expect}, allocator {alloc.ref_count}")
    free = alloc._free
    assert len(set(free)) == len(free), "free list duplicates"
    assert not (set(free) & set(alloc.ref_count)), "freed block still referenced"
    if alloc.num_blocks is not None:
        assert len(free) + len(alloc.ref_count) == alloc.num_blocks


@settings(deadline=None, max_examples=25)
@given(data=st.data())
def test_refcount_invariants_under_churn(data):
    """Random admit(match+grow)/finish(insert)/free/swap/evict churn: no
    leaks, no double-free, shared pages never scribbled (grown blocks are
    always private; matched blocks carry exactly the matched tokens)."""
    bs = data.draw(st.integers(1, 4))
    pool = data.draw(st.integers(8, 32))
    mem = _mem(bs=bs, pool=pool)
    alphabet = st.integers(0, 2)  # tiny vocab -> heavy prefix collisions
    active = {}  # rid -> token list (prompt)
    parked = {}  # rid -> token list while swapped out
    content = {}  # bid -> token chunk written there (live blocks only)
    next_rid = 0
    step = 0

    def drop_dead_content():
        live = set(mem.allocator.ref_count)
        for b in list(content):
            if b not in live:
                del content[b]

    for _ in range(data.draw(st.integers(5, 30))):
        step += 1
        op = data.draw(st.sampled_from(
            ["admit", "finish", "free", "swap_out", "swap_in", "evict"]))
        if op == "admit":
            n_tok = data.draw(st.integers(1, 3 * bs + 1))
            toks = [data.draw(alphabet) for _ in range(n_tok)]
            rid = next_rid
            next_rid += 1
            matched = mem.match_prefix(rid, toks, max_tokens=len(toks) - 1,
                                       step=step)
            t = mem.allocator.tables.get(rid)
            copies = mem.drain_prefix_copies()
            nf = matched // bs  # fully adopted blocks; a tail is a COW copy
            if matched:
                # adopted pages hold exactly the matched tokens (trie keys)
                for i, b in enumerate(t.blocks[:nf]):
                    assert content[b] == tuple(toks[i * bs:(i + 1) * bs]), (
                        "cache handed back a scribbled/mismatched page")
            if matched % bs:
                # mid-block resume: exactly one copy intent for this rid,
                # source page carries the matched tokens, destination is a
                # freshly minted private page (shared pages never scribbled)
                assert len(copies) == 1
                crid, src, dst, p = copies[0]
                assert crid == rid and p == matched % bs
                assert t.blocks[nf] == dst and len(t.blocks) == nf + 1
                assert mem.allocator.ref_count[dst] == 1
                assert content[src][:p] == tuple(toks[nf * bs:nf * bs + p])
                # COW copy + this request's own prefill leave the private
                # page holding this prompt's tokens
                content[dst] = tuple(toks[nf * bs:(nf + 1) * bs])
            else:
                assert copies == []
            before = list(t.blocks) if t else []
            try:
                mem.on_prefill(rid, len(toks) - matched)
            except OutOfBlocks:
                if rid in mem.allocator.tables:
                    mem.free(rid)
                continue
            t = mem.allocator.tables[rid]
            for i, b in enumerate(t.blocks):
                if b in before[:len(before)]:
                    continue
                # grown blocks are freshly minted and private: writing them
                # can never scribble a shared page
                assert mem.allocator.ref_count[b] == 1
                assert b not in content
                content[b] = tuple(toks[i * bs:(i + 1) * bs])
            active[rid] = toks
        elif op == "finish" and active:
            rid = data.draw(st.sampled_from(sorted(active)))
            mem.insert_prefix(rid, active[rid], step=step)
            for node_bid in mem.prefix.block_ids():
                assert node_bid in mem.allocator.ref_count
        elif op == "free" and active:
            rid = data.draw(st.sampled_from(sorted(active)))
            mem.free(rid)
            del active[rid]
        elif op == "swap_out" and active:
            rid = data.draw(st.sampled_from(sorted(active)))
            mem.swap_out(rid)
            parked[rid] = active.pop(rid)
        elif op == "swap_in" and parked:
            rid = data.draw(st.sampled_from(sorted(parked)))
            rec = mem.swapped[rid]
            kept_before = {i: rec.table.blocks[i]
                           for i, k in enumerate(rec.kept) if k}
            try:
                mem.swap_in(rid)
            except OutOfBlocks:
                continue
            toks = parked.pop(rid)
            t = mem.allocator.tables[rid]
            for i, b in enumerate(t.blocks):
                if i in kept_before:
                    # kept (shared) pages re-enter with their original ids
                    # and their contents were never touched
                    assert b == kept_before[i]
                    assert content[b] == tuple(toks[i * bs:(i + 1) * bs])
                else:
                    # spilled pages restore into fresh private ids (the
                    # engine scatters the host copy here)
                    assert mem.allocator.ref_count[b] == 1
                    content[b] = tuple(toks[i * bs:(i + 1) * bs])
            active[rid] = toks
        elif op == "evict":
            mem.prefix.evict(data.draw(st.integers(1, 4)))
        drop_dead_content()
        _audit(mem)

    # teardown: everything releases, nothing leaks
    for rid in list(active):
        mem.free(rid)
    for rid in list(parked):
        mem.drop_swapped(rid)
    mem.prefix.clear()
    _audit(mem)
    assert mem.allocator.used_blocks == 0
    assert mem.allocator.free_blocks == pool
    assert mem.allocator.allocated_blocks_total == mem.allocator.freed_blocks_total


# ---------------------------------------------------------------------------
# occupancy counts shared pages once
# ---------------------------------------------------------------------------


def test_occupancy_counts_shared_pages_once():
    mem = _mem(bs=4, pool=16)
    toks = list(range(500, 512))  # 3 blocks
    mem.on_prefill(0, 12)
    mem.insert_prefix(0, toks)
    for rid in (1, 2):
        assert mem.match_prefix(rid, toks + [8] * 4) == 12
        mem.on_prefill(rid, 4)
    # 3 shared + 2 private blocks; per-table summing would claim 11
    assert mem.device_blocks == 5
    assert mem.device_tokens == 12 + 4 + 4
    assert mem.projected_blocks([]) == 5
    assert 0.0 <= mem.fragmentation() < 1.0
    assert mem.shared_overlap_tokens([0, 1, 2]) == 2 * 12


def test_swapped_shared_pages_stay_projected():
    """A swapped table's kept pages still occupy the pool: projections see
    them, and the restore needs only the spilled pages + decode growth."""
    mem = _mem(bs=4, pool=16)
    toks = list(range(700, 708))
    mem.on_prefill(0, 8)
    mem.insert_prefix(0, toks)
    assert mem.match_prefix(1, toks + [1] * 8) == 8
    mem.on_prefill(1, 8)  # 2 private tail blocks
    used = mem.projected_blocks([])
    moved = mem.swap_out(1)
    assert moved == 8  # only the private tail crossed the host link
    assert mem.projected_blocks([]) == used - 2  # kept pages still counted
    assert mem.swap_in_extra_blocks(1) == 3  # 2 spilled + 1 decode growth
    assert mem.swap_host_bytes(1) == 2 * 4 * mem.kv_bytes_per_token
    mem.swap_in(1)
    assert mem.restored_host_bytes(1) == 2 * 4 * mem.kv_bytes_per_token
    assert mem.tokens_of(1) == 16


# ---------------------------------------------------------------------------
# engine: token identity with the cache on vs off
# ---------------------------------------------------------------------------


def _serial(model, params, req):
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


def _run_engine(model, params, reqs, **sched_kw):
    cfg = dict(chunk_size=16, max_decode_batch=3, prefetch_buffer_bytes=1 << 20,
               max_concurrent_prefills=2, kv_block_size=4)
    cfg.update(sched_kw)
    eng = Engine(model, params, SchedulerConfig(**cfg), max_len=MAX_LEN)
    assert eng.attn_kernel == "paged"
    for r in reqs:
        eng.submit(Request(rid=r.rid, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens))
    eng.run(max_steps=800)
    return eng


@pytest.fixture(scope="module")
def reduced_model():
    cfg = reduce_config(get_config("llama3.1-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_prefix_cache_token_identical(reduced_model):
    """Greedy outputs with the radix cache enabled match the serial
    reference exactly; the cache demonstrably hits and skips prefill."""
    cfg, model, params = reduced_model
    reqs = shared_prefix_requests(n=4, shared_len=24, unique_len=8,
                                  max_new_tokens=5, jitter=2, seed=3,
                                  vocab_size=cfg.vocab_size)
    expected = {r.rid: _serial(model, params, r) for r in reqs}
    eng = _run_engine(model, params, reqs, enable_prefix_cache=True)
    stats = eng.scheduler.stats
    assert stats.prefix_hits > 0, "shared prefixes never hit the cache"
    assert stats.prefix_hit_tokens > 0
    off = _run_engine(model, params, reqs, enable_prefix_cache=False)
    assert off.scheduler.stats.prefill_tokens > stats.prefill_tokens
    for r in reqs:
        got = eng.scheduler.requests[r.rid].output
        assert got == expected[r.rid], (
            f"rid={r.rid}: cached {got} != serial {expected[r.rid]}")
        assert off.scheduler.requests[r.rid].output == expected[r.rid]


@pytest.mark.parametrize("preemption", ["recompute", "swap"])
def test_engine_prefix_cache_oversubscribed_identity(reduced_model, preemption):
    """Cache + an over-subscribed 16-page pool + preemption: shared pages
    survive swap round trips (kept references) and eviction pressure, and
    outputs stay token-identical to the serial reference."""
    cfg, model, params = reduced_model
    reqs = shared_prefix_requests(n=4, shared_len=20, unique_len=8,
                                  max_new_tokens=5, jitter=2, seed=11,
                                  vocab_size=cfg.vocab_size)
    expected = {r.rid: _serial(model, params, r) for r in reqs}
    eng = _run_engine(model, params, reqs, enable_prefix_cache=True,
                      num_kv_blocks=16, preemption=preemption)
    stats = eng.scheduler.stats
    assert eng.num_pool_pages < eng.n_slots * eng.pages_per_slot
    assert stats.prefix_hits > 0
    assert stats.out_of_block_stalls > 0 or stats.preemptions > 0, (
        "a 16-page pool under shared-prefix load never felt pressure")
    for r in reqs:
        got = eng.scheduler.requests[r.rid].output
        assert got == expected[r.rid], (
            f"{preemption} rid={r.rid}: {got} != serial {expected[r.rid]}")
    assert not eng.swap_store, "host tier still holds unrestored KV"


def test_engine_mid_block_prefix_resume_token_identity(reduced_model):
    """A shared prefix that ends INSIDE a page: the admission fast-forwards
    to the exact matched token (3 full pages + 2 tokens here), the engine
    copies the partial page copy-on-write, and greedy outputs still match
    the serial reference token for token."""
    cfg, model, params = reduced_model
    rng = np.random.default_rng(17)
    base = rng.integers(0, cfg.vocab_size, size=26).tolist()
    tail = [(t + 1) % cfg.vocab_size for t in base[14:24]]  # diverges at 14
    reqs = [
        Request(rid=0, prompt=list(base), max_new_tokens=5),
        Request(rid=1, prompt=base[:14] + tail, max_new_tokens=5),
    ]
    expected = {r.rid: _serial(model, params, r) for r in reqs}
    eng = Engine(model, params,
                 SchedulerConfig(chunk_size=16, max_decode_batch=3,
                                 prefetch_buffer_bytes=1 << 20,
                                 max_concurrent_prefills=2, kv_block_size=4,
                                 enable_prefix_cache=True),
                 max_len=MAX_LEN)
    # run rid 0 to completion FIRST so its prompt is fully cached, then
    # admit rid 1 whose shared prefix stops mid-page
    eng.submit(Request(rid=0, prompt=list(reqs[0].prompt), max_new_tokens=5))
    eng.run(max_steps=200)
    eng.submit(Request(rid=1, prompt=list(reqs[1].prompt), max_new_tokens=5))
    eng.run(max_steps=200)
    stats = eng.scheduler.stats
    assert stats.prefix_hit_tokens == 14, "mid-block tail not matched"
    assert stats.prefix_hit_tokens % 4 == 2  # genuinely non-block-aligned
    assert not eng.scheduler.mem.pending_prefix_copies, "copy intent leaked"
    for r in reqs:
        got = eng.scheduler.requests[r.rid].output
        assert got == expected[r.rid], (
            f"rid={r.rid}: {got} != serial {expected[r.rid]}")


# ---------------------------------------------------------------------------
# scheduler-level: multi-turn hits, watermark, metrics surface
# ---------------------------------------------------------------------------


def _drive(sched, max_steps=2000):
    step = 0
    while sched.has_work and step < max_steps:
        plan = sched.next_step(now=float(step))
        if plan is None:
            break
        for rid in plan.decode_rids:
            sched.requests[rid].output.append(0)
        for rid in plan.finishing_rids:
            sched.requests[rid].output.append(0)
        sched.complete_step(plan, now=float(step))
        step += 1
    return step


def test_multi_turn_resubmission_hits():
    """Turn k's prompt extends turn k-1's: the radix cache serves the
    conversation history from shared pages."""
    sched = Scheduler(
        SchedulerConfig(chunk_size=32, max_decode_batch=4, kv_block_size=4,
                        max_concurrent_prefills=2, enable_prefix_cache=True),
        CFG,
    )
    for r in multi_turn_requests(n_users=2, n_turns=3, turn_len=12,
                                 response_len=6, max_new_tokens=3, seed=5):
        sched.add_request(r)
    _drive(sched)
    st_ = sched.stats
    assert all(r.state == State.DONE for r in sched.requests.values())
    assert st_.prefix_hits > 0
    # each turn's history grows, so later turns skip ever more tokens
    assert st_.prefix_hit_tokens >= st_.prefix_hits * 4
    m = summarize(sched.requests.values(), horizon=1.0, sched_stats=st_,
                  chunk_size=32)
    assert m["prefix_hit_rate"] == st_.prefix_hit_rate()
    assert m["prefix_fill_bytes_saved"] > 0


def test_admission_watermark_stalls_and_completes():
    """Below the free-page low-watermark, NEW admissions defer (surfaced in
    watermark_stalls) but running work drains and everything completes."""
    sched = Scheduler(
        SchedulerConfig(chunk_size=8, max_decode_batch=4, kv_block_size=4,
                        num_kv_blocks=8, admission_watermark=4,
                        max_concurrent_prefills=2),
        CFG,
    )
    for i in range(4):
        sched.add_request(Request(rid=i, prompt=[0] * 10, max_new_tokens=3))
    _drive(sched)
    assert all(r.state == State.DONE for r in sched.requests.values())
    assert sched.stats.watermark_stalls > 0
    m = summarize(sched.requests.values(), horizon=1.0,
                  sched_stats=sched.stats, chunk_size=8)
    assert m["watermark_stalls"] == float(sched.stats.watermark_stalls)


def test_watermark_never_gates_idle_system():
    """A watermark larger than the pool must not deadlock an empty system."""
    sched = Scheduler(
        SchedulerConfig(chunk_size=8, max_decode_batch=2, kv_block_size=4,
                        num_kv_blocks=4, admission_watermark=99),
        CFG,
    )
    sched.add_request(Request(rid=0, prompt=[0] * 8, max_new_tokens=2))
    _drive(sched)
    assert sched.requests[0].state == State.DONE


def test_prefetch_demand_dedupes_shared_prefix():
    """The prefetch plan's demand denominator counts a shared physical page
    once; coverage stays <= 1 even when per-request residency double-counts."""
    sched = Scheduler(
        SchedulerConfig(chunk_size=64, max_decode_batch=4, kv_block_size=4,
                        max_concurrent_prefills=2, enable_prefix_cache=True,
                        prefetch_buffer_bytes=1 << 20),
        CFG,
    )
    for r in shared_prefix_requests(n=3, shared_len=16, unique_len=6,
                                    max_new_tokens=6, seed=2):
        sched.add_request(r)
    covs = []
    step = 0
    while sched.has_work and step < 300:
        plan = sched.next_step(now=float(step))
        if plan is None:
            break
        if plan.prefetch is not None and len(plan.decode_rids) > 1:
            covs.append(plan.prefetch.coverage)
            assert plan.prefetch.coverage <= 1.0
        for rid in plan.decode_rids:
            sched.requests[rid].output.append(0)
        for rid in plan.finishing_rids:
            sched.requests[rid].output.append(0)
        sched.complete_step(plan, now=float(step))
        step += 1
    assert covs, "no multi-decode steps observed"
    assert sched.stats.prefix_hits > 0


# ---------------------------------------------------------------------------
# satellites: HBM pool sizing + workload generators
# ---------------------------------------------------------------------------


def test_hbm_kv_pool_blocks_sizing():
    from repro.sim.hardware import TPUV6E

    full = get_config("llama3.1-8b")
    blocks = hbm_kv_pool_blocks(TPUV6E.hbm_bytes, full, block_size=16)
    # 32 GB minus ~16 GB of weights over 128 KB/token * 16-token pages
    weights = full.param_count() * 2
    kv_tok = full.kv_bytes_per_token_layer * full.n_attn_layers
    assert blocks == (TPUV6E.hbm_bytes - weights) // (16 * kv_tok)
    assert 0 < blocks * 16 * kv_tok <= TPUV6E.hbm_bytes - weights + 16 * kv_tok
    assert hbm_kv_pool_blocks(TPUV6E.hbm_bytes, get_config("mamba2-2.7b"),
                              block_size=16) is None  # attention-free


def test_sized_kv_pool_caps_and_floors():
    from repro.launch.serve import sized_kv_pool

    full = get_config("llama3.1-8b")
    # realistic serving shape: HBM budget binds below the dense equivalent
    pool, basis = sized_kv_pool(full, "tpuv6e-like", max_batch=32,
                                max_len=131072, kv_block=16)
    assert basis == "hbm" and pool < 32 * 131072 // 16
    assert pool >= 131072 // 16  # still holds one max_len context
    # reduced CPU shape: dense equivalent binds (HBM budget is huge)
    red = reduce_config(full)
    pool, basis = sized_kv_pool(red, "tpuv6e-like", max_batch=4,
                                max_len=256, kv_block=4)
    assert basis == "dense" and pool == 4 * 256 // 4


def test_device_kv_pool_blocks_caps_and_floors():
    from repro.configs.reduced import cut_depth
    from repro.launch.serve import device_kv_pool_blocks

    cfg = cut_depth(get_config("llama3.1-8b"), 16)
    # one v5e after 16 layers of bf16 weights: the dense layout binds
    pool, basis = device_kv_pool_blocks(cfg, 7 * 2**30, n_rows=520, max_batch=8,
                                        max_len=8192, kv_block=16)
    assert basis == "dense" and pool == 8 * 8192 // 16
    # a tighter device: its budget binds, and still holds one context
    pool, basis = device_kv_pool_blocks(cfg, 2 * 2**30, n_rows=520, max_batch=8,
                                        max_len=8192, kv_block=16)
    assert basis == "device" and 8192 // 16 <= pool < 8 * 8192 // 16
    assert pool * 16 * 64 * 2**10 < 2 * 2**30  # 64 KiB of KV per token
    with pytest.raises(ValueError, match="one max_len context"):
        device_kv_pool_blocks(cfg, 2**28, n_rows=520, max_batch=8,
                              max_len=8192, kv_block=16)


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_location(env_dir, monkeypatch):
    import os

    import jax

    from repro.launch import serve

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        serve.use_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    if env_dir is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
    else:
        assert got is None  # JAX reads the variable itself


def test_shared_prefix_workload_shapes():
    reqs = shared_prefix_requests(n=5, shared_len=32, unique_len=8,
                                  max_new_tokens=4, seed=1)
    heads = {tuple(r.prompt[:32]) for r in reqs}
    assert len(heads) == 1  # one system prompt
    assert len({tuple(r.prompt) for r in reqs}) == 5  # unique suffixes
    assert all(r.arrival_time == 0.0 for r in reqs)


def test_multi_turn_workload_shapes():
    reqs = multi_turn_requests(n_users=2, n_turns=3, turn_len=10,
                               response_len=4, seed=1)
    assert len(reqs) == 6
    by_user = [reqs[0:3], reqs[3:6]]
    for turns in by_user:
        for a, b in zip(turns, turns[1:]):
            assert b.prompt[:len(a.prompt)] == a.prompt  # history extends
            assert len(b.prompt) == len(a.prompt) + 4 + 10
