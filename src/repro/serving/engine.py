"""Continuous-batching serving engine driven by the packing-prefetch scheduler.

Two execution modes:
  * packed   — one jitted ``packed_step`` per cycle: decode tokens + every
    packed prefill segment share every linear/FFN/MoE matmul (true packing).
    Used for attention-family archs.
  * two_call — decode batch call + one prefill call per packed segment, for
    SSM/hybrid and encoder-decoder archs whose mixers need contiguous
    per-segment scans.

Packed attention runs the **ragged paged path by default**
(``attn_kernel="paged"``) over a **physically paged KV pool**: the cache is
allocated as ``(num_kv_blocks + 1, page_size, ...)`` pages per cache key
(the +1 is the scratch page dead table entries and padding rows point at),
and ``block_mirror`` — a device-resident ``(n_slots+1, max_blocks)`` int32
array re-synced every step across alloc/free/swap/preemption — carries the
allocator's **actual** block ids, so pages are relocatable and the pool may
be genuinely over-subscribed (total pages far below ``n_slots * max_len /
page_size``; two long requests can share a pool larger than either's
``max_len`` share). ``packed_step`` scatters the step's new KV through the
mirror and attends through it — each row reads only its own pages up to its
own position (kernels/paged_attention.py on TPU, the bounded jnp oracle on
CPU). Swap preemption gathers/scatters whole pages per the table, and
swap-in lands host KV in whatever fresh pages the allocator mints.
``attn_kernel="dense"`` restores the seed's dense (slot, max_len) storage
and rectangular gather.

Asynchronous prefetch (``SchedulerConfig.async_prefetch``): the scheduler
issues next-step transfer intents (swap-in restores, prefix re-adoptions)
through the in-flight/landed ledger while this step runs. The engine
realizes them by *staging*: each predicted restore's host KV is converted to
device arrays right after this step's compute is dispatched — JAX dispatch
is asynchronous, so the host->device copy overlaps the in-flight compute —
and the ledger transfer is landed once the staged buffer exists. The
consuming step's ``_apply_swaps`` then scatters from the staged device copy
(device-to-device, no host link on the critical path); an unpredicted
restore falls back to the synchronous host copy, and ``_verify_landed``
asserts no step ever reads pages whose transfer has not landed. Invariant:
staged and synchronous restores scatter byte-identical values, so greedy
outputs are token-identical with async prefetch on or off.

Either way the Scheduler (repro.core.scheduler) decides step composition and
prefetch plans, so service-level behaviour (Figs 7/8) is policy-identical to
the simulator. Correctness is proven by tests/test_engine.py: packed
continuous batching reproduces a serial per-request engine token-for-token.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packed_step import (
    STEP_SCOPES,
    PagedView,
    packed_step,
    supports_packed,
)
from repro.core.scheduler import Scheduler, SchedulerConfig, StepPlan
from repro.memory.prefetch_queue import ADOPT, SWAP_IN
from repro.models.model import Model
from repro.obs.attribution import (
    PREFETCH_STAGE as ATTR_PREFETCH_STAGE,
    SWAP_IN as ATTR_SWAP_IN,
    SWAP_OUT as ATTR_SWAP_OUT,
)
from repro.obs.trace import (
    LANE_COMPUTE,
    LANE_HOST,
    LANE_HOST_LINK,
    LANE_PREFETCH_STAGE,
    LANE_SCHED,
    LANE_STEP,
    NOOP,
    hlo_op_scopes,
    span,
)
from repro.serving.request import Request, State

ATTN_KERNELS = ("auto", "paged", "dense")


def _batch_axis(cache_key: str) -> int:
    # prefix caches: (B, ...); period/encdec caches are layer-stacked: (L, B, ...)
    return 0 if cache_key == "prefix" else 1


def _page_bucket(n: int) -> int:
    """Pow2-padded page count for swap transfers (bounds jit recompiles of
    the fused page movers as contexts grow)."""
    m = 8
    while m < n:
        m *= 2
    return m


def _lane_tiles(width: int) -> int:
    """A latent pool leaf's width: whole 128-lane tiles."""
    return -(-width // 128) * 128


def pool_bytes_per_token_layer(cfg) -> int:
    """bf16 bytes one attention layer's page pool holds per token: the K/V
    heads, or (MLA) the latent and rope keys each padded to whole lane
    tiles (``_init_page_pool``)."""
    if cfg.mla:
        return 2 * (_lane_tiles(cfg.kv_lora_rank) + _lane_tiles(cfg.qk_rope_head_dim))
    return cfg.kv_bytes_per_token_layer


def _init_page_pool(model, n_pages: int, page_size: int, dtype):
    """Allocate KV as a physical page pool: every GQA cache leaf becomes a
    head-major (n_pages, heads, page_size, head_dim) array, every MLA latent
    leaf (n_pages, page_size, width) with the width padded to whole
    128-lane tiles (the 64 rope keys take 128, the tail zero), so that one
    page is one tile-aligned copy for the latent kernel (period caches keep
    their leading layer axis). The shapes come from ``model.init_cache`` — one batch row
    of ``n_pages * page_size`` tokens, traced, never allocated — regrouped
    so each page is an independently addressable unit the block tables can
    name in any order."""
    flat = jax.eval_shape(lambda: model.init_cache(1, n_pages * page_size, dtype))

    def to_pool(key, leaf):
        ax = _batch_axis(key)  # batch (=1) at ax, token axis at ax+1
        lead, rest = leaf.shape[:ax], leaf.shape[ax + 2:]
        if len(rest) == 2:  # (heads, head_dim): head-major pages
            shape = lead + (n_pages, rest[0], page_size, rest[1])
        else:  # latent width: no head axis
            shape = lead + (n_pages, page_size, _lane_tiles(rest[0]))
        return jnp.zeros(shape, leaf.dtype)

    return {
        k: jax.tree.map(lambda l, k=k: to_pool(k, l), flat[k]) for k in flat
    }


def _mask_tree(new, old, mask, axis):
    def sel(n, o):
        shape = [1] * n.ndim
        shape[axis] = mask.shape[0]
        return jnp.where(mask.reshape(shape), n, o)

    return jax.tree.map(sel, new, old)


def _take_slot(tree, slot, axis):
    return jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=axis), tree
    )


def _put_slot(full, part, slot, axis):
    return jax.tree.map(
        lambda f, p: jax.lax.dynamic_update_slice_in_dim(f, p.astype(f.dtype), slot, axis=axis),
        full, part,
    )


@jax.jit
def _add_counts(rows, hit, step_rows):
    return rows + step_rows, hit + jnp.count_nonzero(step_rows).astype(hit.dtype)


class _CountedStep:
    """A MoE model's jitted packed step, called as a dense model's step is:
    ``(logits, cache)``. The jitted step also returns the (MoE layers,
    n_experts) count of real rows routed to each expert; each call adds it
    on the device to ``rows``, and the number of experts it gave a row to
    (over MoE layers) to ``hit``. Nothing is fetched until ``totals``."""

    def __init__(self, jitted, n_moe: int, n_experts: int):
        self.jitted = jitted
        self.__name__ = jitted.__name__  # the compiled module's name
        self.rows = jnp.zeros((n_moe, n_experts), jnp.int32)
        self.hit = jnp.zeros((), jnp.int32)

    def __call__(self, *args):
        logits, cache, step_rows = self.jitted(*args)
        self.rows, self.hit = _add_counts(self.rows, self.hit, step_rows)
        return logits, cache

    def totals(self):
        """(rows routed to each expert of each MoE layer, experts hit summed
        over steps and MoE layers) since the engine was built."""
        rows, hit = jax.device_get((self.rows, self.hit))
        return np.asarray(rows, np.int64), int(hit)

    def lower(self, *args):
        return self.jitted.lower(*args)

    def _cache_size(self):
        return self.jitted._cache_size()


class Engine:
    def __init__(
        self,
        model: Model,
        params,
        sched_cfg: SchedulerConfig,
        max_len: int,
        cache_dtype=jnp.float32,
        eos_id: Optional[int] = None,
        attn_kernel: str = "auto",
        tracer=None,  # a repro.obs TraceRecorder (wall clock) — records the
        # step's phase spans (see ``step``), request lifecycles, and the
        # transfer ledger
    ):
        if attn_kernel not in ATTN_KERNELS:
            raise ValueError(f"unknown attn_kernel {attn_kernel!r}; want one of {ATTN_KERNELS}")
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.max_len = max_len
        self.eos_id = eos_id
        self.packed_mode = supports_packed(model.cfg)
        self.n_slots = sched_cfg.max_decode_batch
        self.bucket = self.n_slots + sched_cfg.chunk_size
        self.steps_run = 0
        # host seconds and calls per step phase, cumulative (``span``)
        self.span_s: Dict[str, float] = {}
        self.span_n: Dict[str, int] = {}
        self._spans = (self.span_s, self.span_n)
        self.prefetch_log: List[float] = []
        # swap-style preemption: host-DRAM copies of spilled KV (whole pages
        # in paged mode, slot rows in dense mode), keyed by rid — the "host
        # tier" of the memory subsystem
        self.swap_store: Dict[int, dict] = {}
        # async prefetch: device-resident staged copies of predicted
        # swap-in restores, keyed by rid. Created by _issue_prefetch while
        # the issuing step's compute is still in flight; consumed (popped)
        # by _apply_swaps at the restoring step.
        self._staged: Dict[int, dict] = {}

        # ragged paged attention is the packed default; it needs the page
        # size (= allocator block size) to tile max_len exactly
        self.page_size = sched_cfg.kv_block_size
        on_tpu = jax.default_backend() == "tpu"
        if attn_kernel == "auto":
            # the dense gather is a CPU debug path: on the chip a packed
            # arch serves through the paged kernel or the check below
            # refuses to start
            attn_kernel = (
                "paged" if self.packed_mode and (
                    on_tpu or max_len % self.page_size == 0)
                else "dense"
            )
        if attn_kernel == "paged" and not (
            self.packed_mode and max_len % self.page_size == 0
        ):
            raise ValueError(
                "attn_kernel='paged' needs packed mode and max_len divisible "
                f"by kv_block_size (max_len={max_len}, block={self.page_size})"
            )
        if sched_cfg.enable_prefix_cache and attn_kernel != "paged":
            # dense slot caches and two-call SSM states have no shared pages
            # a forked block table could point at — skipping "cached" tokens
            # would read garbage KV
            raise ValueError(
                "enable_prefix_cache requires the physically paged engine "
                "path (attn_kernel='paged'); dense/two-call KV has no "
                "copy-on-write pages to share")
        self.attn_kernel = attn_kernel

        if self.attn_kernel == "paged":
            # physically paged KV: the pool is num_kv_blocks relocatable
            # pages (default: the dense layout's capacity) + 1 scratch page.
            # Backing the allocator with the same bound makes OutOfBlocks a
            # real admission signal instead of bookkeeping fiction.
            pps = self.pages_per_slot = max_len // self.page_size
            pool_pages = sched_cfg.num_kv_blocks
            if pool_pages is None:
                pool_pages = self.n_slots * pps
                sched_cfg = dataclasses.replace(sched_cfg, num_kv_blocks=pool_pages)
            if pool_pages < pps:
                raise ValueError(
                    f"num_kv_blocks={pool_pages} cannot hold one max_len "
                    f"context ({pps} pages of {self.page_size} tokens)"
                )
            self.num_pool_pages = pool_pages
            self._scratch_page = pool_pages  # the extra page past the pool
            self.cache = _init_page_pool(
                model, pool_pages + 1, self.page_size, cache_dtype
            )
            # device mirror of the allocator's block tables: one row per
            # slot holding *real* physical page ids; dead entries (and the
            # whole scratch row padding tokens write through) -> scratch
            self.block_mirror = np.full(
                (self.n_slots + 1, pps), self._scratch_page, np.int32
            )
            # fused page movers for swap traffic (the paged analogue of the
            # dense path's _gather_slot/_scatter_slot): one compiled call +
            # one host transfer per swapped request, ids padded to a pow2
            # bucket of scratch pages so recompiles stay bounded. The page
            # axis leads every pool leaf (after the layer axis), so whole
            # pages — all heads — move together. Writers donate the pool:
            # it is updated in place, never copied.
            def gather_pages(cache, ids):
                return {
                    k: jax.tree.map(
                        lambda l, a=_batch_axis(k): jnp.take(l, ids, axis=a),
                        cache[k],
                    )
                    for k in cache
                }

            def scatter_pages(cache, part, ids):
                return {
                    k: jax.tree.map(
                        lambda l, h, a=_batch_axis(k): l.at[
                            (slice(None),) * a + (ids,)
                        ].set(h.astype(l.dtype)),
                        cache[k], part[k],
                    )
                    for k in cache
                }

            self._gather_pages = jax.jit(gather_pages)
            self._scatter_pages = jax.jit(scatter_pages, donate_argnums=(0,))
        else:
            # dense slot storage: +1 scratch row for padding tokens
            self.cache = model.init_cache(self.n_slots + 1, max_len, cache_dtype)

        self.sched_cfg = sched_cfg
        self.trace = tracer if tracer is not None else NOOP
        self.scheduler = Scheduler(sched_cfg, model.cfg, tracer=self.trace)

        if self.packed_mode:
            if self.attn_kernel == "paged":
                use_pallas = on_tpu
                page = self.page_size
                # the unified mixed-batch attention path: ONE compiled call
                # serves decode rows and packed prefill chunks alike, driven
                # by the plan's segment layout (cu_q_lens / kv_lens /
                # seg_slots). ``qb`` — the pow2 q-block rows bucket — is
                # static so the kernel tiles each segment's queries exactly.
                def paged_step(p, c, t, s, pos, bt, cq, kl, ss, qb):
                    logits, cache, rows = packed_step(
                        model, p, c, t, s, pos,
                        paged=PagedView(bt, page, use_kernel=use_pallas,
                                        cu_q_lens=cq, kv_lens=kl,
                                        seg_slots=ss, q_block=qb),
                    )
                    return (logits, cache) if rows is None else (logits, cache, rows)

                self._packed = jax.jit(paged_step, static_argnums=(9,),
                                       donate_argnums=(1,))
                n_moe = sum(1 for sp in model.cfg.layer_specs if sp.ffn == "moe")
                if n_moe:
                    self._packed = _CountedStep(self._packed, n_moe, model.cfg.n_experts)

                # mid-block prefix resume: batched copy-on-write page
                # duplication (gather-then-scatter in ONE compiled call, so
                # every source is read from the pre-copy array before any
                # destination is written)
                def copy_pages(cache, src, dst):
                    return {
                        k: jax.tree.map(
                            lambda l, a=_batch_axis(k): l.at[
                                (slice(None),) * a + (dst,)
                            ].set(jnp.take(l, src, axis=a)),
                            cache[k],
                        )
                        for k in cache
                    }

                self._copy_pages = jax.jit(copy_pages, donate_argnums=(0,))
            else:
                def dense_step(p, c, t, s, pos):
                    return packed_step(model, p, c, t, s, pos)[:2]

                self._packed = jax.jit(dense_step)
        else:
            # per-arch decode/prefill entry points exist ONLY for the
            # two-call (SSM/hybrid/encdec) path — attention-family archs run
            # everything through the single packed_step call site
            self._decode = jax.jit(model.decode_step)
            self._prefill = jax.jit(model.prefill)
            # jitted slot zero-reset for two-call re-prefills (slot reuse):
            # the zeros tree is built inside the compiled call, not rebuilt
            # per use
            def reset_slot(cache, slot):
                return {
                    k: _put_slot(
                        cache[k],
                        jax.tree.map(
                            lambda l: jnp.zeros_like(
                                jax.lax.slice_in_dim(
                                    l, 0, 1, axis=_batch_axis(k))
                            ),
                            cache[k],
                        ),
                        slot,
                        _batch_axis(k),
                    )
                    for k in cache
                }

            self._reset_slot = jax.jit(reset_slot)
        if self.attn_kernel != "paged":
            # fused single-call slot movers for dense swap traffic: one
            # compiled gather/scatter over the whole cache tree per swapped
            # request (the paged path moves pages via _gather/_scatter_pages)
            def gather_slot(cache, slot):
                return {k: _take_slot(cache[k], slot, _batch_axis(k))
                        for k in cache}

            def scatter_slot(cache, part, slot):
                return {k: _put_slot(cache[k], part[k], slot, _batch_axis(k))
                        for k in cache}

            self._gather_slot = jax.jit(gather_slot)
            self._scatter_slot = jax.jit(scatter_slot)

    # ------------------------------------------------------------------ API
    def submit(self, req: Request) -> None:
        self.scheduler.add_request(req)

    def run(self, max_steps: int = 10_000) -> None:
        while self.scheduler.has_work and self.steps_run < max_steps:
            if self.step(now=float(self.steps_run)) is None:
                break

    def shutdown(self, reason: str = "shutdown") -> int:
        """Graceful teardown (KeyboardInterrupt/SIGTERM in launch.serve):
        cancel every non-terminal request, purge their host-tier state, and
        cancel outstanding ledger intents so a flushed trace is fully
        terminal.  Returns the number of requests cancelled."""
        n = self.scheduler.cancel_all(reason, now=float(self.steps_run))
        self._purge_released()
        self.scheduler.prefetch_queue.cancel_outstanding(reason)
        return n

    def _purge_released(self) -> None:
        """Drop host swap copies and staged device buffers of requests the
        scheduler released (cancellations, swap->recompute fallbacks) — the
        engine-side half of clean cancellation."""
        for rid, _reason in self.scheduler.drain_released():
            self.swap_store.pop(rid, None)
            self._staged.pop(rid, None)

    def register_metrics(self, reg) -> None:
        """Engine-side gauges for the typed metrics registry: step count,
        host-tier occupancy, and (paged mode) pool capacity/peak pressure."""
        reg.counter("engine_steps", "steps", "engine steps executed").inc(
            self.steps_run)
        for name, secs in self.span_s.items():
            reg.counter(f"span_s.{name}", "s",
                        f"host seconds in {name}").inc(secs)
            reg.counter(f"span_n.{name}", "calls",
                        f"calls of {name}").inc(self.span_n[name])
        reg.gauge("engine_swap_store_entries", "requests",
                  "host-tier KV copies currently held").set(
                      float(len(self.swap_store)))
        if self.attn_kernel == "paged":
            reg.gauge("kv_pool_pages", "pages",
                      "physical pages in the paged KV pool").set(
                          float(self.num_pool_pages))
            reg.gauge("kv_pool_peak_used", "pages",
                      "peak pages simultaneously allocated").set(
                          float(self.scheduler.mem.allocator.peak_used_blocks))
        counts = self.expert_counts()
        if counts is not None:
            expert_rows, experts_hit = counts
            reg.counter("moe.routed_rows", "rows",
                        "real rows routed to experts, summed over MoE layers "
                        "and top-k").inc(int(expert_rows.sum()))
            reg.counter("moe.experts_hit", "experts",
                        "experts with at least one routed row, summed over "
                        "steps and MoE layers").inc(experts_hit)
            for e, n in enumerate(expert_rows.sum(axis=0)):
                reg.counter(f"moe.expert_rows.{e}", "rows",
                            f"rows routed to expert {e}, summed over MoE "
                            "layers").inc(int(n))
        if self.scheduler.injector.enabled:
            self.scheduler.injector.register_metrics(reg)
        self.scheduler.ledger.register_metrics(reg)

    def expert_counts(self):
        """A MoE model's ``_CountedStep.totals()`` (summed on the device,
        fetched here); None for a dense model."""
        step = getattr(self, "_packed", None)  # two-call engines have none
        if isinstance(step, _CountedStep):
            return step.totals()
        return None

    def step_op_scopes(self, nb: int, sb: int, qb: int) -> Dict[str, str]:
        """{HLO instruction name: named scope} of the paged packed step
        compiled for ``nb`` table columns, ``sb`` segments and q-block
        ``qb`` (``_run_packed``'s buckets), so the device operations of a
        profiler trace can be summed by ``STEP_SCOPES``. Lowers and compiles
        the step from shapes alone (a compile-cache hit where the step ran);
        nothing runs."""
        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        rows = i32(self.bucket)
        lowered = self._packed.lower(
            jax.tree.map(like, self.params), jax.tree.map(like, self.cache),
            rows, rows, rows, i32(self.n_slots + 1, nb), i32(sb + 1), i32(sb),
            i32(sb), qb)
        return hlo_op_scopes(lowered.compile().as_text(), STEP_SCOPES)

    def attribution_aggregates(self) -> Dict[str, float]:
        """The engine's independently accumulated byte counters, keyed by
        the ``repro.obs.attribution.AGG_RULES`` names the conservation
        checker maps onto ledger causes. Feed to
        ``ByteLedger.record_totals`` / ``conservation_errors``."""
        sched = self.scheduler
        mem = sched.mem
        return {
            "attn_read_bytes": float(sched.stats.attn_tokens_touched
                                     * mem.kv_bytes_per_token),
            "prefix_saved_bytes": float(sched.stats.prefix_fill_bytes_saved),
            "swap_out_bytes": float(mem.swap_out_bytes_total),
            "swap_in_bytes": float(mem.swap_in_bytes_total),
            "swapped_bytes": float(mem.swap_out_bytes_total
                                   + mem.swap_in_bytes_total),
            "retry_refetch_bytes": float(
                sched.prefetch_queue.stats.bytes_refetched),
        }

    # ----------------------------------------------------------------- steps
    def step(self, now: float = 0.0) -> Optional[StepPlan]:
        """Run one scheduled step. Its host work is tiled by spans (``span``:
        profiler annotations, ``span_s``/``span_n``, recorder spans), all
        inside ``engine.step``: ``sched.next_step``,
        ``engine.prefix_copies``, ``engine.swaps``, then the packed phases
        (``engine.pack``, ``engine.dispatch``, ``engine.fetch``,
        ``engine.sample``, see ``_run_packed``) or ``engine.run_two_call``,
        ``engine.prefetch`` and ``sched.complete_step``."""
        tr, acc = self.trace, self._spans
        step = self.steps_run
        with span("engine.step", LANE_STEP, tr, acc, step_num=step,
                  step=step) as args:
            with span("sched.next_step", LANE_SCHED, tr, acc, step=step):
                plan = self.scheduler.next_step(now)
                # even a None plan may have cancelled requests
                self._purge_released()
            if plan is None:
                return None
            args.update(tokens=plan.total_tokens,
                        decodes=len(plan.decode_rids),
                        prefill_tokens=plan.total_prefill_tokens)
            if plan.prefetch is not None:
                self.prefetch_log.append(plan.prefetch.coverage)
            # copy-on-write page duplication for mid-block prefix resumes
            # MUST run before any other device write this step: sources are
            # cached pages whose ids were valid at plan time, and neither
            # swap traffic nor the compute scatter has touched the pool yet
            with span("engine.prefix_copies", LANE_COMPUTE, tr, acc,
                      step=step):
                self._apply_prefix_copies(plan)
            with span("engine.swaps", LANE_HOST_LINK, tr, acc, step=step,
                      swap_out=len(plan.swapped_out),
                      swap_in=len(plan.swapped_in)):
                self._apply_swaps(plan)
                self._verify_landed(plan)
            if self.packed_mode:
                self._run_packed(plan)
            else:
                with span("engine.run_two_call", LANE_COMPUTE, tr, acc,
                          step=step, tokens=plan.total_tokens):
                    self._run_two_call(plan)
            # stage next step's predicted transfers NOW: the compute above
            # is dispatched but (on an async backend) still in flight, so
            # these host->device copies ride under it
            with span("engine.prefetch", LANE_PREFETCH_STAGE, tr, acc,
                      step=step, issued=len(plan.issued)):
                self._issue_prefetch(plan)
            with span("sched.complete_step", LANE_SCHED, tr, acc, step=step):
                self.scheduler.complete_step(plan, now)
                # emit the step's attribution instant at the same point in
                # the event stream as the sim (right after complete_step),
                # so the two backends' sched sequences stay position-aligned
                # for --compare
                self.scheduler.ledger.record_step(tr, plan.step)
            self.steps_run += 1
        return plan

    # ----------------------------------------------------------------- swaps
    def block_spans(self, rid: int) -> List[Tuple[int, int, int]]:
        """Map a request's block table onto its logical token axis:
        [(block_id, start_token, n_tokens)] — which physical pool page (or
        dense-row page in dense mode) holds which span of the context."""
        mem = self.scheduler.mem
        table = mem.allocator.tables.get(rid)
        if table is None:
            return []
        bs = mem.block_size
        return [
            (bid, i * bs, min(bs, table.num_tokens - i * bs))
            for i, bid in enumerate(table.blocks)
        ]

    def _apply_prefix_copies(self, plan: StepPlan) -> None:
        """Materialize the plan's mid-block prefix-cache resumes: each entry
        ``(rid, src_block, dst_block, n_tokens)`` copies a cached page whose
        FIRST ``n_tokens`` match the admitted prompt into the fresh private
        tail page the admission minted. Whole pages are copied (one batched
        gather-then-scatter), which is safe: positions past ``n_tokens`` are
        masked until the request's own prefill overwrites them, and shared
        source pages are never written — copy-on-write, not adoption."""
        if self.attn_kernel != "paged" or not plan.prefix_copies:
            return
        scratch = self._scratch_page
        n = len(plan.prefix_copies)
        m = _page_bucket(n)
        src = np.full((m,), scratch, np.int32)
        dst = np.full((m,), scratch, np.int32)
        for i, (_rid, s, d, _p) in enumerate(plan.prefix_copies):
            src[i] = s
            dst[i] = d
        self.cache = self._copy_pages(
            self.cache, jnp.asarray(src), jnp.asarray(dst)
        )

    def _apply_swaps(self, plan: StepPlan) -> None:
        """Execute the plan's swap traffic on the KV storage before the
        compute call. Paged mode moves whole pages — but only the *spilled*
        (private) ones: shared pages (forked prefixes, radix-cache blocks)
        stay device-resident across the round trip, pinned by the detach
        record's kept references. A swap-out gathers exactly the spilled
        pages the victim's record names; a swap-in scatters the host copies
        into the fresh pages ``attach()`` minted at the same table
        positions — physical ids differ across the round trip, contents
        stay token-identical. Dense mode moves whole slot rows. Outs run
        first so a swap-in may reuse just-freed pages/slots within the same
        step."""
        # byte attribution: debit the host-link swap traffic at apply time,
        # from the memory manager's own spill records — independent of the
        # sim's pricing-loop debits, so their per-step equality (checked by
        # check_trace --compare) is a genuine cross-check
        led = self.scheduler.ledger
        for rid, _slot in plan.swapped_out:
            led.debit(plan.step, ATTR_SWAP_OUT,
                      self.scheduler.mem.swap_host_bytes(rid))
        for rid, _slot in plan.swapped_in:
            led.debit(plan.step, ATTR_SWAP_IN,
                      self.scheduler.mem.restored_host_bytes(rid))
        if self.attn_kernel == "paged":
            mem = self.scheduler.mem
            scratch = self._scratch_page
            for rid, _slot in plan.swapped_out:
                rec = mem.swapped[rid].record
                idx = rec.spilled_indices
                if not idx:  # fully shared table: nothing crosses the link
                    self.swap_store[rid] = {"kv": None, "idx": idx}
                    continue
                blocks = [rec.table.blocks[i] for i in idx]
                n = len(blocks)
                ids = np.full((_page_bucket(n),), scratch, np.int32)
                ids[:n] = blocks
                gathered = self._gather_pages(self.cache, jnp.asarray(ids))
                # the pow2 id bucket bounds jit recompiles, but only the
                # live pages cross the host link: slice on device, then
                # transfer — matching the block-rounded bytes the sim prices
                self.swap_store[rid] = {"idx": idx, "kv": jax.device_get({
                    k: jax.tree.map(
                        lambda l, a=_batch_axis(k): jax.lax.slice_in_dim(
                            l, 0, n, axis=a),
                        gathered[k],
                    )
                    for k in gathered
                })}
            for rid, _slot in plan.swapped_in:
                entry = self.swap_store.pop(rid)
                staged = self._staged.pop(rid, None)
                saved, idx = entry["kv"], entry["idx"]
                if not idx:
                    continue  # every page stayed resident; table reuses them
                blocks = mem.allocator.tables[rid].blocks
                # scatter into the *fresh* pages attach() minted at the same
                # table positions the spill recorded (kept pages re-entered
                # with their original ids and need no copy). The host copy
                # holds exactly the spilled pages; pad it (and the id
                # vector, with the scratch page) back to the pow2 bucket so
                # the compiled scatter is reused — scratch receives zeros it
                # never meaningfully serves. If the table already grew one
                # extra page for this step's decode write, that page needs
                # no restore: it only covers positions at/after the restored
                # context, which stay masked until the compute writes them.
                n = len(idx)
                m = _page_bucket(n)
                ids = np.full((m,), scratch, np.int32)
                ids[:n] = [blocks[i] for i in idx]
                if staged is not None:
                    # async prefetch landed this restore: the host copy is
                    # already on device (bucket-padded at stage time), so
                    # the scatter is device-to-device — no host link on the
                    # critical path. Values are byte-identical to the
                    # synchronous branch below.
                    saved = staged
                elif m != n:
                    saved = {
                        k: jax.tree.map(
                            lambda h, a=_batch_axis(k): np.concatenate(
                                [h, np.zeros(
                                    h.shape[:a] + (m - n,) + h.shape[a + 1:],
                                    h.dtype)], axis=a),
                            saved[k],
                        )
                        for k in saved
                    }
                self.cache = self._scatter_pages(self.cache, saved,
                                                 jnp.asarray(ids))
            return
        for rid, slot in plan.swapped_out:
            self.swap_store[rid] = jax.device_get(
                self._gather_slot(self.cache, jnp.int32(slot))
            )
        for rid, slot in plan.swapped_in:
            saved = self.swap_store.pop(rid)
            staged = self._staged.pop(rid, None)
            if staged is not None:
                saved = staged  # pre-staged on device by _issue_prefetch
            self.cache = self._scatter_slot(self.cache, saved, jnp.int32(slot))

    # ------------------------------------------------------------- prefetch
    def _issue_prefetch(self, plan: StepPlan) -> None:
        """Realize the ledger transfers this plan issued for the NEXT step.

        SWAP_IN: the predicted restore's host pages are put on device as a
        staged copy (padded to the same pow2 page bucket ``_apply_swaps``
        scatters with, so the compiled scatter is reused verbatim). ADOPT:
        the matched radix blocks are already device-resident pages — no
        bytes cross a link, the intent lands immediately. Either way the
        transfer is LANDED before any later step may consume it, so the
        readable() invariant holds by construction on the engine.

        Under fault injection ``attempt_land`` arbitrates: a doomed or
        delayed attempt does NOT land (its staged copy — the half-finished
        DMA — is dropped), and the shared retry clock re-surfaces the
        transfer in a later plan's ``retried`` list, where this same loop
        re-stages it from the still-intact host copy."""
        q = self.scheduler.prefetch_queue
        for t in list(plan.issued) + list(plan.retried):
            if t.kind == SWAP_IN:
                entry = self.swap_store.get(t.rid)
                if entry is None:
                    continue  # intent outlived the store (defensive)
                if not q.attempt_land(t, plan.step):
                    # injected failure or delay: the transfer stays in the
                    # ledger; whatever staging a prior attempt did is torn
                    # down so the retry re-copies from the host tier
                    self._staged.pop(t.rid, None)
                    continue
                if t.rid not in self._staged:
                    if self.attn_kernel == "paged":
                        saved, idx = entry["kv"], entry["idx"]
                        if saved is None:
                            continue  # fully shared table: nothing to move
                        n = len(idx)
                        m = _page_bucket(n)
                        if m != n:
                            saved = {
                                k: jax.tree.map(
                                    lambda h, a=_batch_axis(k): np.concatenate(
                                        [h, np.zeros(
                                            h.shape[:a] + (m - n,)
                                            + h.shape[a + 1:], h.dtype)],
                                        axis=a),
                                    saved[k],
                                )
                                for k in saved
                            }
                        self._staged[t.rid] = jax.tree.map(jnp.asarray, saved)
                    else:
                        self._staged[t.rid] = jax.tree.map(jnp.asarray, entry)
                    # attribution: these host->device bytes moved ahead of
                    # their consuming step (ADOPT moves nothing; a re-land
                    # over an intact staged copy moves nothing new)
                    self.scheduler.ledger.debit(
                        plan.step, ATTR_PREFETCH_STAGE, t.nbytes)
            elif t.kind == ADOPT:
                q.attempt_land(t, plan.step)

    def _verify_landed(self, plan: StepPlan) -> None:
        """Guard before attention reads the mirror: no request this step
        touches may have an outstanding (issued / in-flight, not landed)
        transfer. The scheduler consumes transfers at restore/adoption time,
        and _issue_prefetch lands everything it stages, so this never fires
        in a correct engine — it exists to turn a broken overlap schedule
        into a loud error instead of silently stale KV."""
        q = self.scheduler.prefetch_queue
        rids = set(plan.decode_rids)
        rids.update(s.rid for s in plan.prefill_segments)
        for rid in sorted(rids):
            for kind in (SWAP_IN, ADOPT):
                if not q.readable(rid, kind):
                    raise RuntimeError(
                        f"async prefetch invariant violated: request {rid} "
                        f"is scheduled this step but its {kind} transfer "
                        "has not landed")

    def _sample_rows(self, logits_rows: np.ndarray) -> np.ndarray:
        """(rows, vocab) -> (rows,) token ids. The engine's single sampling
        hook: greedy by default, override for other decoders. All execution
        paths route their gathered logits rows through here."""
        return np.argmax(logits_rows, axis=-1)

    def _append(self, req: Request, tok: int) -> None:
        req.output.append(tok)
        if self.eos_id is not None and tok == self.eos_id:
            req.finished = True  # complete_step checks the flag explicitly

    # ---------------------------------------------------------------- packed
    def _sync_block_mirror(self, plan: StepPlan) -> int:
        """Re-sync the device block-table mirror from the allocator's tables
        for this step's active slots. Freed/preempted/swapped-out slots fall
        back to the scratch page; live slots copy their table's **actual
        physical page ids** — the scheduler grew tables at plan time, so the
        ids already cover the pages this step's writes scatter into.
        Returns the longest context (tokens) any row touches this step."""
        m = self.block_mirror
        pps = self.pages_per_slot
        m[:] = self._scratch_page
        sch = self.scheduler
        need_tokens: Dict[int, int] = {}
        for slot, rid in zip(plan.decode_slots, plan.decode_rids):
            need_tokens[slot] = sch.requests[rid].next_decode_pos + 1
        for seg in plan.prefill_segments:
            need_tokens[seg.slot] = max(need_tokens.get(seg.slot, 0),
                                        seg.start + seg.length)
        tables = sch.mem.allocator.tables
        for slot, req in sch.active.items():
            table = tables.get(req.rid)
            if table is None:
                continue
            n = min(pps, table.num_blocks)
            if n:
                m[slot, :n] = table.blocks[:n]
        return max(need_tokens.values(), default=1)

    def _nb_bucket(self, max_tokens: int) -> int:
        """Block-table columns for this step: ceil(longest context / page),
        rounded up to a power of two (bounds jit recompiles as contexts
        grow), capped at the per-slot page count."""
        need = -(-max(max_tokens, 1) // self.page_size)
        nb = 8
        while nb < need:
            nb *= 2
        return min(nb, self.pages_per_slot)

    def _run_packed(self, plan: StepPlan) -> None:
        """One packed step in four spans: ``engine.pack`` builds the host
        arrays (and re-syncs the block mirror), ``engine.dispatch`` enqueues
        the jitted step and the sampled-row gather, ``engine.fetch`` waits
        for the sampled rows on the host (the device step ends inside it)
        and ``engine.sample`` picks and appends the tokens."""
        tr, acc = self.trace, self._spans
        step = self.steps_run
        sch = self.scheduler
        with span("engine.pack", LANE_HOST, tr, acc, step=step):
            N = self.bucket
            tokens = np.zeros((N,), np.int32)
            slots = np.full((N,), self.n_slots, np.int32)  # scratch by default
            positions = np.zeros((N,), np.int32)

            nd = len(plan.decode_slots)
            for i, (slot, rid) in enumerate(zip(plan.decode_slots, plan.decode_rids)):
                req = sch.requests[rid]
                tokens[i] = req.output[-1]
                positions[i] = req.next_decode_pos
                slots[i] = slot
            row = nd
            last_rows = {}  # rid -> row of its segment's last token (finishing only)
            for seg in plan.prefill_segments:
                req = sch.requests[seg.rid]
                tokens[row : row + seg.length] = req.prefill_slice(seg.start, seg.length)
                positions[row : row + seg.length] = np.arange(seg.start, seg.start + seg.length)
                slots[row : row + seg.length] = seg.slot
                if seg.finishes:
                    last_rows[seg.rid] = row + seg.length - 1
                row += seg.length

            paged = self.attn_kernel == "paged"
            if paged:
                max_ctx = self._sync_block_mirror(plan)
                nb = self._nb_bucket(max_ctx)
                # segment layout for the unified mixed-batch attention call:
                # the scheduler stamped cu_q_lens/cu_kv_lens on the plan in
                # the SAME order the rows above were packed (decodes, then
                # prefill segments), so the arrays ship verbatim — single
                # source of truth shared with the sim's cost model. Padding
                # segments are zero-width (q_len = kv_len = 0) and own the
                # scratch slot.
                s_real = nd + len(plan.prefill_segments)
                kv_real = plan.kv_lens
                sb = 8
                while sb < s_real:
                    sb *= 2
                cu_q = np.full((sb + 1,), plan.cu_q_lens[-1], np.int32)
                cu_q[: s_real + 1] = plan.cu_q_lens
                kv_lens = np.zeros((sb,), np.int32)
                kv_lens[:s_real] = kv_real
                seg_slots = np.full((sb,), self.n_slots, np.int32)
                seg_slots[:nd] = plan.decode_slots
                for i, seg in enumerate(plan.prefill_segments):
                    seg_slots[nd + i] = seg.slot
                # static q-block: pow2 bucket of the longest segment so a
                # decode-only step compiles with qb=1 while chunked prefills
                # tile in blocks — (nb, sb, qb) are the only shape-bearing
                # keys
                qb = 1
                max_q = int(max(np.diff(cu_q[: s_real + 1]), default=1))
                while qb < max_q:
                    qb *= 2
                assert int(cu_q[s_real]) == row, (
                    f"plan row layout mismatch: cu_q_lens end {cu_q[s_real]} "
                    f"!= packed rows {row}")
                if nd:
                    assert np.array_equal(kv_lens[:nd], positions[:nd] + 1), (
                        "decode kv_lens drifted from engine positions")
            rows = list(range(nd)) + list(last_rows.values())
            rids = list(plan.decode_rids) + list(last_rows.keys())
        with span("engine.dispatch", LANE_COMPUTE, tr, acc, step=step,
                  tokens=plan.total_tokens):
            if paged:
                logits, self.cache = self._packed(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(slots), jnp.asarray(positions),
                    jnp.asarray(self.block_mirror[:, :nb]), jnp.asarray(cu_q),
                    jnp.asarray(kv_lens), jnp.asarray(seg_slots), qb,
                )
            else:
                logits, self.cache = self._packed(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(slots), jnp.asarray(positions),
                )
            # one device->host transfer of just the sampled rows, then one
            # vectorized argmax (greedy) over all of them
            if rows:
                picked = logits[jnp.asarray(rows, jnp.int32)]
        if not rows:
            return
        with span("engine.fetch", LANE_COMPUTE, tr, acc, step=step):
            picked = np.asarray(picked)
        with span("engine.sample", LANE_HOST, tr, acc, step=step):
            for rid, tok in zip(rids, self._sample_rows(picked)):
                self._append(sch.requests[rid], int(tok))

    # -------------------------------------------------------------- two-call
    def _run_two_call(self, plan: StepPlan) -> None:
        sch = self.scheduler
        B = self.n_slots + 1
        if plan.decode_slots:
            tokens = np.zeros((B, 1), np.int32)
            index = np.zeros((B,), np.int32)
            mask = np.zeros((B,), bool)
            for slot, rid in zip(plan.decode_slots, plan.decode_rids):
                req = sch.requests[rid]
                tokens[slot, 0] = req.output[-1]
                index[slot] = req.next_decode_pos
                mask[slot] = True
            logits, new_cache = self._decode(
                self.params, jnp.asarray(tokens), self.cache, jnp.asarray(index)
            )
            m = jnp.asarray(mask)
            self.cache = {
                k: _mask_tree(new_cache[k], self.cache[k], m, _batch_axis(k))
                for k in self.cache
            }
            # gather the live slots' logits in one transfer, vectorized argmax
            picked = np.asarray(logits[jnp.asarray(plan.decode_slots, jnp.int32)])
            for rid, tok in zip(plan.decode_rids, self._sample_rows(picked)):
                self._append(sch.requests[rid], int(tok))

        for seg in plan.prefill_segments:
            req = sch.requests[seg.rid]
            slot = seg.slot
            if seg.start == 0:
                # slot reuse / re-prefill after preemption: SSM/conv states
                # are additive — reset the row (single precompiled call)
                self.cache = self._reset_slot(self.cache, jnp.int32(slot))
            chunk = req.prefill_slice(seg.start, seg.length)
            batch = {"tokens": jnp.asarray(np.asarray(chunk, np.int32)[None])}
            if self.cfg.encdec:
                batch["frames"] = (
                    jnp.asarray(req.frames[None])
                    if req.frames is not None
                    else jnp.zeros((1, self.cfg.frontend_len, self.cfg.d_model), jnp.float32)
                )
            sub = {
                k: _take_slot(self.cache[k], slot, _batch_axis(k)) for k in self.cache
            }
            logits, sub = self._prefill(
                self.params, batch, sub, jnp.int32(seg.start)
            )
            self.cache = {
                k: _put_slot(self.cache[k], sub[k], slot, _batch_axis(k)) for k in self.cache
            }
            if seg.finishes:
                self._append(req, int(self._sample_rows(np.asarray(logits)[:1])[0]))
