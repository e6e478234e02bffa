"""On-chip smoke test: serve Llama3.1-8B at its published widths on one TPU.

    python chip_smoke.py

One process, no children. Phases, in order; any failure exits nonzero:

  (a) report the device JAX sees; anything but a TPU exits nonzero here;
  (b) the mixed-batch Pallas kernel against the ``mixed_attention_ref``
      oracle at Llama3.1-8B widths (KV=8, G=4, d=128, page 16): decode
      rows plus one 512-token prefill chunk over shuffled pages, in bf16;
  (c) ``launch.serve`` end to end at the published widths, 16 of 32
      layers, random weights from ``--seed``: every request completes on
      the paged path, and the compiled packed step holds the kernel;
  (d) ``launch.serve`` again on an over-subscribed pool with swap
      preemption, async prefetch and the prefix cache: swaps and prefix
      hits both happen, and every request completes.

The last line of standard output is one JSON object naming the device.
JAX's persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
says, and otherwise in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch import serve  # noqa: E402  (fails outside a checkout)

# phase (b): the kernel's output is bf16, so one bf16 ulp of an O(1) value
# (2^-8) plus accumulation-order differences; the oracle runs at full f32
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2

MAX_NEW = 32
SERVE_ARGS = [
    "--arch", "llama3.1-8b", "--layers", "16", "--requests", "8",
    "--max-len", "8192", "--chunk", "512", "--max-batch", "8",
    "--max-new", str(MAX_NEW), "--kv-block", "16",
]


COMPILE_S = [0.0]  # backend compile seconds so far (cache hits excluded)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILE_S[0] += secs


def phase(name):
    print(f"[chip_smoke] {name}", flush=True)
    return time.perf_counter(), COMPILE_S[0]


def done(label, t, **fields):
    wall, compiled = time.perf_counter() - t[0], COMPILE_S[0] - t[1]
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[chip_smoke] {label} passed: {extra} wall_s={wall:.1f} "
          f"compile_s={compiled:.1f}", flush=True)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def check_kernel(jax, jnp, np):
    """Phase (b): one mixed step, decode rows + a 512-token prefill chunk
    after a 1024-token cached prefix, every segment's pages shuffled across
    the pool and the dead pages filled with garbage."""
    from repro.kernels.paged_attention import mixed_paged_attention
    from repro.kernels.ref import mixed_attention_ref

    KV, G, d, page = 8, 4, 128, 16
    rng = np.random.default_rng(0)
    segs = [(1, 37), (1, 512), (1, 1000), (1, 2049), (1, 4096), (1, 3),
            (1, 777), (512, 1024 + 512)]  # (q_len, kv_len)
    nb = max(-(-kv // page) for _, kv in segs)
    live = [-(-kv // page) for _, kv in segs]
    P = sum(live) + 16
    ids = rng.permutation(P)
    dead = ids[sum(live):]
    tables = rng.choice(dead, size=(len(segs), nb)).astype(np.int32)
    off = 0
    for s, n in enumerate(live):
        tables[s, :n] = ids[off:off + n]
        off += n
    k = rng.standard_normal((P, KV, page, d), np.float32)
    v = rng.standard_normal((P, KV, page, d), np.float32)
    k[dead], v[dead] = 1e4, -1e4
    n_rows = sum(q for q, _ in segs)
    q = rng.standard_normal((n_rows, KV, G, d), np.float32)
    cu = np.concatenate([[0], np.cumsum([q for q, _ in segs])]).astype(np.int32)
    kv_lens = np.asarray([kv for _, kv in segs], np.int32)
    q, k, v = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    got = mixed_paged_attention(q, k, v, jnp.asarray(cu), jnp.asarray(kv_lens),
                                jnp.asarray(tables), qb=512)
    # the oracle expands every row over its whole table, so it runs on
    # groups of at most 16 rows (each a causal segment of its own) to stay
    # well inside HBM
    ref = jax.jit(mixed_attention_ref)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    want = []
    with jax.default_matmul_precision("highest"):
        for s, (q_len, kv) in enumerate(segs):
            for j0 in range(0, q_len, 16):
                j1 = min(q_len, j0 + 16)
                want.append(ref(
                    qf[cu[s] + j0:cu[s] + j1], kf, vf,
                    jnp.asarray([0, j1 - j0], jnp.int32),
                    jnp.asarray([kv - q_len + j1], jnp.int32),
                    jnp.asarray(tables[s:s + 1])))
    want = jnp.concatenate(want)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), "kernel output is not finite"
    err = float(np.max(np.abs(got - want)))
    print(f"[chip_smoke] kernel vs oracle: rows={n_rows} segments={len(segs)} "
          f"nb={nb} max_abs_err={err:.3e} (atol={KERNEL_ATOL}, rtol={KERNEL_RTOL})")
    np.testing.assert_allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL)
    return err


def check_outputs(eng, max_new: int) -> int:
    """Every request finished with ``max_new`` token ids inside the
    vocabulary; returns the number of tokens generated."""
    vocab = eng.cfg.vocab_size
    reqs = list(eng.scheduler.requests.values())
    for r in reqs:
        assert len(r.output) == max_new, (r.rid, len(r.output))
        assert all(0 <= t < vocab for t in r.output), (r.rid, r.output)
    return sum(len(r.output) for r in reqs)


def packed_step_hlo(jax, jnp, eng) -> str:
    """Compiled text of the engine's packed step at a decode-only shape."""
    sds = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    n, sb, nb = eng.bucket, 8, eng.pages_per_slot
    return eng._packed.lower(
        sds(eng.params), sds(eng.cache), i32(n), i32(n), i32(n),
        i32(eng.n_slots + 1, nb), i32(sb + 1), i32(sb), i32(sb), 1,
    ).compile().as_text()


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    serve.use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    phase("(a) device")
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}
    print(f"[chip_smoke] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if dev.platform != "tpu":
        print("[chip_smoke] no TPU: this smoke runs only on the chip",
              file=sys.stderr)
        return 2

    t = phase("(b) mixed kernel vs oracle at 8B widths")
    err = check_kernel(jax, jnp, np)
    done("(b)", t, max_abs_err=f"{err:.3e}")

    t = phase("(c) launch.serve, 16 layers at published widths")
    eng, m = serve.main(SERVE_ARGS)
    assert m["completed"] == m["submitted"] == 8, m
    assert eng.attn_kernel == "paged", eng.attn_kernel
    n_tok = check_outputs(eng, MAX_NEW)
    text = packed_step_hlo(jax, jnp, eng)
    assert "tpu_custom_call" in text and "mixed_paged_attention" in text, (
        "packed step does not call the mixed_paged_attention kernel")
    c_peak = eng.scheduler.mem.allocator.peak_used_blocks
    done("(c)", t, steps=eng.steps_run,
         completed=f"{m['completed']}/{m['submitted']}", tokens=n_tok,
         pool=f"{c_peak}/{eng.num_pool_pages}pages",
         hlo="mixed_paged_attention", peak_bytes_in_use=peak_bytes(dev))
    pages = max(c_peak // 2, eng.pages_per_slot)
    del eng
    gc.collect()

    t = phase("(d) over-subscribed pool: swap, async prefetch, prefix cache")
    eng, m = serve.main(SERVE_ARGS + [
        "--num-kv-blocks", str(pages), "--preemption", "swap",
        "--prefix-cache", "--shared-prefix", "1024"])
    assert m["completed"] == m["submitted"] == 8, m
    assert eng.attn_kernel == "paged", eng.attn_kernel
    n_tok = check_outputs(eng, MAX_NEW)
    assert m["swap_outs"] > 0, "pool never over-subscribed: no swap"
    assert m["prefix_hit_rate"] > 0, "no prefix-cache hit"
    done("(d)", t, steps=eng.steps_run,
         completed=f"{m['completed']}/{m['submitted']}", tokens=n_tok,
         pool=f"{eng.scheduler.mem.allocator.peak_used_blocks}/{pages}pages",
         swaps=int(m["swap_outs"]), prefix_hit_rate=f"{m['prefix_hit_rate']:.2f}",
         overlapped_bytes=int(m["bytes_overlapped"]),
         peak_bytes_in_use=peak_bytes(dev))
    del eng
    gc.collect()

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
