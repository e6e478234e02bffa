"""Serving launcher: packing-prefetch engine over a workload.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.1-8b --reduced \
        --requests 8 --chunk 32

    # published widths, 16 of 32 layers, on one TPU v5e chip
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.1-8b --layers 16 \
        --requests 8 --max-len 8192 --chunk 512 --max-batch 8 --max-new 32

The model is served in bfloat16: weights (drawn directly in bf16 from
``--seed``), activations and KV. ``--reduced`` shrinks widths and depth for
a CPU run; ``--layers N`` cuts depth only, keeping the published widths.

The physical KV page pool is sized from the device that serves: on a device
that reports ``memory_stats()`` (a TPU), its ``bytes_limit`` less the
resident weights and the step's temporaries; elsewhere from the simulator's
HBM budget (``--hw``, Table I archs): capacity minus weights. Either way one
page's full-stack KV bytes divide the budget, capped at the dense-equivalent
layout (``max_batch * max_len`` tokens), which binds on reduced CPU configs
where the budget would dwarf what the slots can address.
``--num-kv-blocks`` overrides the computed size explicitly.

JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.configs.reduced import cut_depth, dropless
from repro.core.packed_step import supports_packed
from repro.core.scheduler import SchedulerConfig
from repro.memory.manager import hbm_kv_pool_blocks
from repro.models import build_model
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import TraceRecorder
from repro.obs.perfetto import dump_json, export_chrome
from repro.serving.engine import Engine, pool_bytes_per_token_layer
from repro.serving.metrics import summarize
from repro.serving.request import Request, State
from repro.serving.workload import shared_prefix_requests
from repro.sim.hardware import HARDWARE


def sized_kv_pool(cfg, hw_name: str, max_batch: int, max_len: int,
                  kv_block: int):
    """(pool_blocks, basis) from the arch's HBM budget, dense-capped."""
    dense_equiv = max_batch * max_len // kv_block
    budget = hbm_kv_pool_blocks(HARDWARE[hw_name].hbm_bytes, cfg, kv_block)
    floor = max(1, max_len // kv_block)  # engine needs one max_len context
    if budget is None:  # attention-free: no paged KV to budget
        return dense_equiv, "dense"
    if budget < floor:
        return floor, "floor"
    if budget < dense_equiv:
        return budget, "hbm"
    return dense_equiv, "dense"


def device_kv_pool_blocks(cfg, free_bytes: int, n_rows: int, max_batch: int,
                          max_len: int, kv_block: int):
    """(pool_blocks, basis) that fit ``free_bytes`` of device memory left
    after the weights, dense-capped. The packed step's temporaries come off
    first: fp32 logits over every packed row (and one working copy), plus
    the one layer's K/V pages the layer scan copies out of the pool per
    page held. 5% stays free for the allocator's own slack."""
    kv_per_token = pool_bytes_per_token_layer(cfg) * cfg.n_attn_layers
    page_bytes = kv_block * kv_per_token * (1 + 1 / max(cfg.n_attn_layers, 1))
    logits = 2 * n_rows * cfg.vocab_size * 4
    usable = int(free_bytes * 0.95) - logits
    dense_equiv = max_batch * max_len // kv_block
    budget = max(0, int(usable // page_bytes))
    floor = max(1, max_len // kv_block)
    if budget < floor:
        raise ValueError(
            f"device memory holds {budget} KV pages after the weights; one "
            f"max_len context needs {floor}")
    if budget < dense_equiv:
        return budget, "device"
    return dense_equiv, "dense"


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself), or else at the
    fixed ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moves never hits."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parents[3]
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def main(argv=None):
    """Parse ``argv`` (default: the command line), serve the workload and
    print the metrics line. Returns ``(engine, metrics)``; exits nonzero if
    a request was left unfinished."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve only the first N layers at the published "
                         "widths (depth-only cut; applied after --reduced)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the workload")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prefetch-mb", type=float, default=0.25)
    ap.add_argument("--policy", choices=["fcfs", "sjf", "priority"], default="fcfs")
    ap.add_argument("--max-prefills", type=int, default=1,
                    help="prefill requests packable into one step")
    ap.add_argument("--kv-capacity", type=int, default=None,
                    help="total KV token budget; exceeding it preempts decodes")
    ap.add_argument("--preemption", choices=["recompute", "swap"], default="recompute",
                    help="drop-and-re-prefill vs spill-to-host preemption")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="paged KV block size in tokens")
    ap.add_argument("--hw", choices=sorted(HARDWARE), default="tpuv6e-like",
                    help="simulated serving hardware whose HBM budget sizes "
                         "the KV page pool (capacity minus weights) when the "
                         "device reports no memory_stats()")
    ap.add_argument("--num-kv-blocks", type=int, default=None,
                    help="explicit physical KV page pool size in blocks "
                         "(paged path; overrides the --hw HBM-budget sizing)."
                         " Smaller pools over-subscribe: admission stalls on"
                         " OutOfBlocks instead of over-allocating")
    ap.add_argument("--attn-kernel", choices=["auto", "paged", "dense"], default="auto",
                    help="packed attention path: ragged block-table (paged) "
                         "vs dense cache gather")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: shared prompt prefixes fork "
                         "cached pages copy-on-write instead of re-prefilling")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="generate prompts sharing a system prefix of this "
                         "many tokens (0 = independent random prompts)")
    ap.add_argument("--admission-watermark", type=int, default=0,
                    help="free-page low-watermark gating NEW admissions "
                         "(blocks); reduces shed/re-admit thrash")
    ap.add_argument("--no-async-prefetch", action="store_true",
                    help="disable one-step-ahead KV transfer staging: swap "
                         "restores and prefix adoptions pay the synchronous "
                         "host-link cost instead of overlapping compute "
                         "(outputs are token-identical either way)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace.json of the run "
                         "(open in ui.perfetto.dev); tracing is off — and "
                         "free — without this flag")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the full metrics summary as NaN-safe JSON "
                         "(non-finite values serialize as null)")
    ap.add_argument("--attribution-json", default=None, metavar="PATH",
                    help="dump the per-step byte-attribution ledger (cause x "
                         "lane x step, plus totals) as NaN-safe JSON "
                         "(docs/observability.md)")
    # robustness layer (docs/robustness.md)
    ap.add_argument("--fault-plan", default=None, metavar="PATH",
                    help="JSON FaultPlan to inject deterministic transfer "
                         "chaos (see repro.robustness.FaultPlan)")
    ap.add_argument("--fail-rate", type=float, default=0.0,
                    help="per-attempt transfer failure probability (builds "
                         "an ad-hoc FaultPlan; ignored with --fault-plan)")
    ap.add_argument("--delay-rate", type=float, default=0.0,
                    help="per-attempt transfer delay probability")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault schedule")
    ap.add_argument("--max-transfer-retries", type=int, default=3,
                    help="failed-transfer retry budget before the swap-in "
                         "falls back to recompute")
    ap.add_argument("--request-timeout", type=float, default=None,
                    help="per-request deadline in engine steps after "
                         "arrival; expired requests are cancelled cleanly")
    ap.add_argument("--degraded-threshold", type=float, default=None,
                    help="rolling transfer-failure rate that trips degraded "
                         "mode (async prefetch off, admissions shed) until "
                         "the rate recovers")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.layers is not None:
        cfg = cut_depth(cfg, args.layers)
    cfg = dropless(cfg)  # serving uses dropless MoE dispatch
    model = build_model(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))

    pool, pool_basis, pool_src = args.num_kv_blocks, "flag", args.hw
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if pool is None and supports_packed(cfg) and args.attn_kernel != "dense":
        if stats and "bytes_limit" in stats:
            jax.block_until_ready(params)
            free = stats["bytes_limit"] - dev.memory_stats()["bytes_in_use"]
            pool_src = dev.device_kind
            pool, pool_basis = device_kv_pool_blocks(
                cfg, free, args.max_batch + args.chunk, args.max_batch,
                args.max_len, args.kv_block)
        else:
            pool, pool_basis = sized_kv_pool(cfg, args.hw, args.max_batch,
                                             args.max_len, args.kv_block)
    fault_plan = None
    if args.fault_plan:
        from repro.robustness import FaultPlan
        fault_plan = FaultPlan.load(args.fault_plan)
    elif args.fail_rate > 0 or args.delay_rate > 0:
        from repro.robustness import FaultPlan
        fault_plan = FaultPlan(seed=args.fault_seed, fail_rate=args.fail_rate,
                               delay_rate=args.delay_rate)
    tracer = TraceRecorder("engine") if args.trace_out else None
    eng = Engine(model, params, SchedulerConfig(
        chunk_size=args.chunk, max_decode_batch=args.max_batch,
        prefetch_buffer_bytes=int(args.prefetch_mb * 2**20),
        max_concurrent_prefills=args.max_prefills, policy=args.policy,
        kv_capacity_tokens=args.kv_capacity, preemption=args.preemption,
        kv_block_size=args.kv_block, num_kv_blocks=pool,
        enable_prefix_cache=args.prefix_cache,
        admission_watermark=args.admission_watermark,
        async_prefetch=not args.no_async_prefetch,
        fault_plan=fault_plan,
        max_transfer_retries=args.max_transfer_retries,
        request_timeout=args.request_timeout,
        degraded_threshold=args.degraded_threshold),
        max_len=args.max_len, cache_dtype=jnp.bfloat16,
        attn_kernel=args.attn_kernel, tracer=tracer)
    rng = np.random.default_rng(args.seed)
    if args.shared_prefix > 0:
        for req in shared_prefix_requests(
                args.requests, shared_len=args.shared_prefix,
                unique_len=max(8, args.max_len // 8),
                max_new_tokens=args.max_new, vocab_size=cfg.vocab_size,
                seed=args.seed):
            eng.submit(req)
    else:
        for rid in range(args.requests):
            L = int(rng.integers(8, args.max_len // 2))
            eng.submit(Request(rid=rid,
                               prompt=rng.integers(0, cfg.vocab_size, L).tolist(),
                               max_new_tokens=args.max_new))
    # graceful shutdown: SIGTERM behaves like ^C — the run loop unwinds,
    # in-flight requests are cancelled cleanly (allocator/ledger/host-tier
    # state released), and the trace/metrics artifacts below still flush
    def _on_sigterm(signum, frame):
        raise KeyboardInterrupt

    prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    interrupted = False
    try:
        eng.run(max_steps=5000)
        if eng.scheduler.has_work:
            # step budget exhausted with work left: cancel the remainder so
            # the trace/ledger flush in a fully terminal state
            n = eng.shutdown("truncated")
            print(f"[launch.serve] step budget exhausted: cancelled {n} "
                  "unfinished request(s)", file=sys.stderr)
    except KeyboardInterrupt:
        interrupted = True
        n = eng.shutdown("interrupt")
        print(f"[launch.serve] interrupted: cancelled {n} in-flight "
              "request(s), flushing artifacts", file=sys.stderr)
    finally:
        signal.signal(signal.SIGTERM, prev_handler)
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    m = summarize(eng.scheduler.requests.values(), horizon=float(max(eng.steps_run, 1)),
                  sched_stats=eng.scheduler.stats, chunk_size=args.chunk,
                  prefetch_stats=eng.scheduler.prefetch_queue.stats,
                  registry=reg)
    if args.trace_out:
        # stamp the run-total attribution instant so tools/check_trace.py
        # can enforce byte conservation on the exported trace
        eng.scheduler.ledger.record_totals(tracer, eng.attribution_aggregates())
        export_chrome(tracer, args.trace_out)
        print(f"[launch.serve] trace written to {args.trace_out}")
    if args.metrics_json:
        dump_json(args.metrics_json, m)
        print(f"[launch.serve] metrics written to {args.metrics_json}")
    if args.attribution_json:
        dump_json(args.attribution_json, eng.scheduler.ledger.as_dict())
        print(f"[launch.serve] attribution ledger written to "
              f"{args.attribution_json}")
    # savings are *realized* only when the ragged paged path actually ran;
    # otherwise the number is what it would have saved
    ragged = eng.packed_mode and eng.attn_kernel == "paged"
    savings = (f"{m['attn_padding_savings']:.2f}" if ragged
               else f"n/a(would_save={m['attn_padding_savings']:.2f})")
    alloc = eng.scheduler.mem.allocator
    pool_rep = (f"pool={alloc.peak_used_blocks}/{alloc.num_blocks}pages"
                f"({pool_basis}:{pool_src}) "
                f"oob_stalls={int(m['out_of_block_stalls'])} "
                f"wm_stalls={int(m['watermark_stalls'])} "
                if ragged else "")
    prefix_rep = (f"prefix_hit_rate={m['prefix_hit_rate']:.2f} "
                  f"prefill_skipped={int(m['prefix_tokens_skipped'])}tok "
                  f"fill_saved={m['prefix_fill_bytes_saved']:.0f}B "
                  if args.prefix_cache else "")
    print(f"[launch.serve] mode={'packed' if eng.packed_mode else 'two_call'} "
          f"attn={eng.attn_kernel} "
          f"policy={args.policy} steps={eng.steps_run} "
          f"completed={m['completed']}/{m['submitted']} "
          f"pack_eff={m['packing_efficiency']:.2f} "
          f"preemptions={int(m['preemptions'])} "
          f"swaps={int(m['swap_outs'])} "
          f"{pool_rep}"
          f"{prefix_rep}"
          f"attn_savings={savings} "
          # coverage over steps with plannable bytes only (vacuous excluded)
          f"prefetch_cov={m['prefetch_coverage']:.2f} "
          f"overlapped={m['bytes_overlapped']:.0f}B "
          f"overlap_eff={m['overlap_efficiency']:.2f} "
          f"async={'off' if args.no_async_prefetch else 'on'}")
    unfinished = sorted(r.rid for r in eng.scheduler.requests.values()
                        if r.state is not State.DONE)
    if unfinished or interrupted:
        print(f"[launch.serve] exiting nonzero: {len(unfinished)} "
              f"unfinished request(s) {unfinished[:16]}"
              f"{'...' if len(unfinished) > 16 else ''}"
              f"{' (interrupted)' if interrupted else ''}", file=sys.stderr)
        sys.exit(1)
    return eng, m


if __name__ == "__main__":
    main()
