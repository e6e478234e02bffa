"""Kernel microbenchmarks (CPU: XLA reference path timing + interpret-mode
correctness cross-check; the Pallas kernels are TPU-target).

The dense-gather vs ragged-paged attention comparison reports both wall time
and *bytes touched* (analytic: the dense path reads every row padded to
S_max, the paged path reads whole pages up to each row's length). With
``--json`` the rows land in BENCH_kernels.json so CI records the perf
trajectory; ``--smoke`` shrinks shapes for the CI lane.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.paged_attention import tokens_touched
from repro.models.flash_xla import flash_sdpa


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(print_fn=print, smoke: bool = False, json_path: Optional[str] = None):
    print_fn("kernel,us_per_call,derived")
    records = []

    def record(name, us, **extra):
        records.append(dict(kernel=name, us_per_call=us, **extra))

    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 4)

    B, S, H, KV, d = 1, (256 if smoke else 1024), 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, d), jnp.float32)

    f_ref = jax.jit(lambda q, k, v: ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)))
    us = _time(f_ref, q, k, v)
    flops = 4 * B * H * S * S * d / 2
    print_fn(f"attention_xla_ref_{S},{us:.0f},{flops/us*1e-3:.1f}GFLOP/s_cpu")
    record("attention_xla_ref", us, gflops_cpu=flops / us * 1e-3)

    qp = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    f_flash = jax.jit(lambda q, k, v: flash_sdpa(q, (k, v), qp, jnp.arange(S),
                                                 scale=d**-0.5, block_q=256, block_k=256))
    us = _time(f_flash, q, k, v)
    print_fn(f"flash_xla_blocked_{S},{us:.0f},{flops/us*1e-3:.1f}GFLOP/s_cpu")
    record("flash_xla_blocked", us, gflops_cpu=flops / us * 1e-3)

    # ------------------------------------------------------------------
    # dense-gather vs ragged paged decode attention at mixed lengths
    # (lengths << S_max: the serving regime the packed engine lives in)
    # ------------------------------------------------------------------
    Bd, Sd, page = (8, 1024, 64) if smoke else (32, 8192, 128)
    kv_elt_bytes = 4  # fp32 pools here
    qd = jax.random.normal(ks[0], (Bd, H, d), jnp.float32)
    kd = jax.random.normal(ks[1], (Bd, Sd, KV, d), jnp.float32)
    vd = jax.random.normal(ks[2], (Bd, Sd, KV, d), jnp.float32)
    # mixed ragged lengths, mean ~Sd/8 — far below the padded extent
    lens_np = np.linspace(page // 2, Sd // 4, Bd).astype(np.int32)
    lengths = jnp.asarray(lens_np)

    f_dense = jax.jit(lambda q, k, v, l: ref.decode_attention_ref(
        q.reshape(Bd, KV, H // KV, d), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), l))
    us_dense = _time(f_dense, qd, kd, vd, lengths)
    dense_tokens = Bd * Sd
    dense_bytes = dense_tokens * KV * d * 2 * kv_elt_bytes  # k + v
    print_fn(f"attn_dense_gather_{Bd}x{Sd//1024}k,{us_dense:.0f},"
             f"{dense_bytes/(us_dense*1e-6)/1e9:.1f}GB/s_cpu")

    # paged path: head-major (pages, KV, page, d) pool view + identity
    # tables bounded to the live context
    pps = Sd // page
    pool_k = kd.reshape(Bd * pps, page, KV, d).transpose(0, 2, 1, 3)
    pool_v = vd.reshape(Bd * pps, page, KV, d).transpose(0, 2, 1, 3)
    nb = int(-(-int(lens_np.max()) // page))
    tables = jnp.asarray(
        (np.arange(Bd)[:, None] * pps + np.arange(nb)[None, :]).astype(np.int32))
    f_paged = jax.jit(lambda q, pk, pv, l, t: ops.paged_attention_rows(q, pk, pv, l, t))
    us_paged = _time(f_paged, qd, pool_k, pool_v, lengths, tables)
    ragged_tokens = tokens_touched(lens_np.tolist(), page)
    ragged_bytes = ragged_tokens * KV * d * 2 * kv_elt_bytes
    print_fn(f"attn_ragged_paged_{Bd}x{Sd//1024}k,{us_paged:.0f},"
             f"bytes_ratio={ragged_bytes/dense_bytes:.3f}")
    assert ragged_bytes < dense_bytes, "ragged path must touch fewer bytes"
    record("attn_dense_gather", us_dense,
           tokens_per_s=dense_tokens / (us_dense * 1e-6),
           kv_tokens_read=dense_tokens, bytes_touched=dense_bytes)
    record("attn_ragged_paged", us_paged,
           tokens_per_s=ragged_tokens / (us_paged * 1e-6),
           kv_tokens_read=ragged_tokens, bytes_touched=ragged_bytes,
           bytes_vs_dense=ragged_bytes / dense_bytes)

    # ------------------------------------------------------------------
    # physically paged KV write: the engine scatters each step's new K/V
    # through the block-table mirror (page id + in-page offset) instead of
    # a dense (slot, position) row write. Shuffled tables = worst-case
    # non-contiguous pool. Also reports pool occupancy: live pages the
    # ragged lengths actually pin vs the dense layout's page budget.
    # ------------------------------------------------------------------
    from repro.core.packed_step import PagedView

    rng_np = np.random.default_rng(0)
    perm_w = rng_np.permutation(Bd * pps)
    tables_w = jnp.asarray(
        np.argsort(perm_w)[(np.arange(Bd)[:, None] * pps
                            + np.arange(pps)[None, :])].astype(np.int32))
    pool_kw = pool_k[jnp.asarray(perm_w)]
    view = PagedView(tables_w, page)
    slots_w = jnp.arange(Bd, dtype=jnp.int32)
    pos_w = lengths  # each row appends at its next position
    vals = jax.random.normal(ks[3], (Bd, KV, d), jnp.float32)
    f_paged_w = jax.jit(lambda pool, v: view.scatter(pool, slots_w, pos_w, v))
    us_pw = _time(f_paged_w, pool_kw, vals)
    f_dense_w = jax.jit(lambda c, v: c.at[slots_w, pos_w].set(v))
    us_dw = _time(f_dense_w, kd, vals)
    live_pages = ragged_tokens // page
    occupancy = live_pages / (Bd * pps)
    print_fn(f"paged_write_scatter_{Bd}rows,{us_pw:.0f},"
             f"dense_write_us={us_dw:.0f};pool_occupancy={occupancy:.3f}")
    record("paged_write_scatter", us_pw, rows=Bd, dense_write_us=us_dw,
           live_pages=live_pages, pool_pages=Bd * pps,
           pool_occupancy=occupancy)
    # scatter parity: the table-routed write lands where the dense write
    # would, page-permutation notwithstanding
    got = np.asarray(f_paged_w(pool_kw, vals))[np.asarray(perm_w).argsort()]
    want = np.asarray(f_dense_w(kd, vals)).reshape(
        Bd * pps, page, KV, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)

    # ------------------------------------------------------------------
    # mixed batch: ONE unified attention call for decode rows + prefill
    # chunks vs the old per-token prefill expansion. The unified path reads
    # each chunk's paged prefix ONCE per chunk; the per-token path re-reads
    # it for every chunk token — the bytes gap is the point of the kernel.
    # ------------------------------------------------------------------
    C = 2
    chunk_len = 128 if smoke else 512
    prefix = 2 * page  # cached context ahead of each chunk
    seg_q = [1] * Bd + [chunk_len] * C
    seg_kv = lens_np.tolist() + [prefix + chunk_len] * C
    Sm = len(seg_q)
    Nm = sum(seg_q)
    nb_m = int(-(-max(seg_kv) // page))
    tables_m = jnp.asarray(
        ((np.arange(Sm) % Bd)[:, None] * pps
         + np.arange(nb_m)[None, :]).astype(np.int32))
    cu_m = np.zeros((Sm + 1,), np.int32)
    cu_m[1:] = np.cumsum(seg_q)
    kv_m = jnp.asarray(np.asarray(seg_kv, np.int32))
    qm = jax.random.normal(ks[3], (Nm, H, d), jnp.float32)
    f_mixed = jax.jit(lambda q, pk, pv, cu, kl, t: ops.mixed_attention_rows(
        q, pk, pv, cu, kl, t, qb=chunk_len))
    us_mix = _time(f_mixed, qm, pool_k, pool_v, jnp.asarray(cu_m), kv_m, tables_m)
    # old path: expand every chunk token to its own row length + table row
    row_len = lens_np.tolist()
    row_tab = [np.asarray(tables_m[s]) for s in range(Bd)]
    for s in range(Bd, Sm):
        for j in range(chunk_len):
            row_len.append(prefix + j + 1)
            row_tab.append(np.asarray(tables_m[s]))
    row_len_j = jnp.asarray(np.asarray(row_len, np.int32))
    row_tab_j = jnp.asarray(np.stack(row_tab))
    f_pt = jax.jit(lambda q, pk, pv, l, t: ops.paged_attention_rows(q, pk, pv, l, t))
    us_pt = _time(f_pt, qm, pool_k, pool_v, row_len_j, row_tab_j)
    # prefill-side KV tokens read (block-rounded): once per CHUNK vs once
    # per chunk TOKEN
    uni_prefill = tokens_touched(seg_kv[Bd:], page)
    pt_prefill = tokens_touched(row_len[Bd:], page)
    assert uni_prefill < pt_prefill, (
        "unified path must read strictly fewer prefill KV bytes")
    kv_row_bytes = KV * d * 2 * kv_elt_bytes
    print_fn(f"attn_mixed_unified_{Bd}d+{C}x{chunk_len}p,{us_mix:.0f},"
             f"prefill_bytes_ratio={uni_prefill/pt_prefill:.3f}")
    print_fn(f"attn_mixed_per_token_{Bd}d+{C}x{chunk_len}p,{us_pt:.0f},"
             f"prefill_kv_tokens={pt_prefill}")
    record("attn_mixed_unified", us_mix,
           tokens_per_s=Nm / (us_mix * 1e-6),
           prefill_kv_tokens_read=uni_prefill,
           prefill_bytes_touched=uni_prefill * kv_row_bytes,
           prefill_bytes_vs_per_token=uni_prefill / pt_prefill)
    record("attn_mixed_per_token", us_pt,
           tokens_per_s=Nm / (us_pt * 1e-6),
           prefill_kv_tokens_read=pt_prefill,
           prefill_bytes_touched=pt_prefill * kv_row_bytes)

    # SSD chunk scan
    Bs, Ss, nh, hd, G, ds = 2, (512 if smoke else 2048), 8, 32, 1, 32
    x = jax.random.normal(ks[0], (Bs, Ss, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, Ss, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (Bs, Ss, G, ds), jnp.float32) * 0.5
    Cm = jax.random.normal(ks[0], (Bs, Ss, G, ds), jnp.float32) * 0.5
    from repro.models.mamba import ssd_chunked
    f_ssd = jax.jit(lambda x, dt, Bm, Cm: ssd_chunked(x, dt, A, Bm, Cm))
    us = _time(f_ssd, x, dt, Bm, Cm)
    print_fn(f"ssd_chunked_xla_{Ss},{us:.0f},{Bs*Ss/(us*1e-6)/1e6:.2f}Mtok/s_cpu")
    record("ssd_chunked_xla", us, mtok_per_s_cpu=Bs * Ss / (us * 1e-6) / 1e6)

    # interpret-mode cross-checks (Pallas kernel == oracle), small shapes
    out = ops.flash_attention_bshd(q[:, :256], k[:, :256], v[:, :256],
                                   interpret=True, block_q=128, block_k=128)
    expect = ref.flash_attention_ref(
        q[:, :256].transpose(0, 2, 1, 3), k[:, :256].transpose(0, 2, 1, 3),
        v[:, :256].transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(out - expect)))
    print_fn(f"pallas_flash_interpret_check,0,max_err={err:.2e}")

    out = ops.paged_attention_rows(
        qd[:4], pool_k, pool_v, lengths[:4], tables[:4], interpret=True)
    expect = ops.paged_attention_rows(qd[:4], pool_k, pool_v, lengths[:4], tables[:4])
    err_p = float(jnp.max(jnp.abs(out - expect)))
    print_fn(f"pallas_paged_interpret_check,0,max_err={err_p:.2e}")
    assert err_p < 2e-5

    # mixed kernel (interpret) == jnp oracle on a tiny decode+chunk batch
    cu_s = jnp.asarray(np.asarray([0, 1, 2, 10], np.int32))
    kv_s = jnp.asarray(np.asarray([int(lens_np[0]), int(lens_np[1]),
                                   prefix + 8], np.int32))
    tab_s = tables_m[:3]
    qs = qm[:10]
    out = ops.mixed_attention_rows(qs, pool_k, pool_v, cu_s, kv_s, tab_s,
                                   qb=8, interpret=True)
    expect = ops.mixed_attention_rows(qs, pool_k, pool_v, cu_s, kv_s, tab_s,
                                      qb=8)
    err_m = float(jnp.max(jnp.abs(out - expect)))
    print_fn(f"pallas_mixed_interpret_check,0,max_err={err_m:.2e}")
    assert err_m < 2e-5

    if json_path:
        with open(json_path, "w") as f:
            json.dump({"smoke": smoke, "kernels": records}, f, indent=2)
        print_fn(f"# wrote {json_path}")
    return True


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI lane)")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="also write records to this JSON file")
    a = ap.parse_args()
    run(smoke=a.smoke, json_path=a.json_path)
