"""Public jit'd wrappers around the Pallas kernels.

Handle layout adaptation from the model's (B, S, H, d) tensors, head-group
padding for MXU alignment, and the interpret switch (CPU validation). On a
CPU-only container the default execution path of the models is XLA; these
wrappers are the TPU-target hot path, validated in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd as _ssd


def flash_attention_bshd(
    q, k, v, *, causal=True, window=None, softcap=None, interpret=False,
    block_q=_fa.DEFAULT_BLOCK_Q, block_k=_fa.DEFAULT_BLOCK_K,
):
    """Model-layout wrapper: q (B,S,H,d), k/v (B,S,KV,d) -> (B,S,H,d)."""
    B, S, H, d = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    pad = (-S) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    o = _fa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, interpret=interpret,
    ).transpose(0, 2, 1, 3)
    return o[:, :S]


def decode_attention_bhd(
    q, k, v, lengths, *, window=None, softcap=None, interpret=False,
    block_k=_dec.DEFAULT_BLOCK_K,
):
    """Model-layout wrapper: q (B,1,H,d), cache k/v (B,S,KV,d), lengths (B,)."""
    B, T, H, d = q.shape
    assert T == 1
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q[:, 0].reshape(B, KV, G, d)
    bk = min(block_k, S)
    pad = (-S) % bk
    kc = k.transpose(0, 2, 1, 3)
    vc = v.transpose(0, 2, 1, 3)
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0)))
    o = _dec.decode_attention(
        qg, kc, vc, lengths.astype(jnp.int32),
        window=window, softcap=softcap, block_k=bk, interpret=interpret,
    )
    return o.reshape(B, 1, H, d)


def paged_attention_rows(
    q, k_pages, v_pages, lengths, block_tables,
    *, window=None, softcap=None, use_kernel=False, interpret=False,
):
    """Per-row layout wrapper: q (N,H,d) + head-major page pool
    (P,KV,page,d), per-row lengths (N,) and block tables (N,nb) -> (N,H,d).
    Each row is a one-token segment of the mixed-batch kernel (the decode
    case), attending ``lengths[n]`` keys."""
    N = q.shape[0]
    return mixed_attention_rows(
        q, k_pages, v_pages, jnp.arange(N + 1, dtype=jnp.int32), lengths,
        block_tables, qb=1, window=window, softcap=softcap,
        use_kernel=use_kernel, interpret=interpret,
    )


def mixed_attention_rows(
    q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
    *, qb=8, window=None, softcap=None, use_kernel=False, interpret=False,
):
    """Packed mixed-batch layout wrapper: q (N,H,d) rows laid out by segment
    (cu_q_lens (S+1,) row offsets; a decode row is a 1-token segment, a
    prefill chunk a longer one), head-major page pool (P,KV,page,d),
    per-SEGMENT kv extents (S,) and block tables (S,nb) -> (N,H,d). ``qb``
    is the static q-block (pow2 >= the longest segment) the kernel tiles
    queries with."""
    N, H, d = q.shape
    KV = k_pages.shape[1]
    G = H // KV
    qg = q.reshape(N, KV, G, d)
    o = _pa.ragged_mixed_attention(
        qg, k_pages, v_pages, cu_q_lens.astype(jnp.int32),
        kv_lens.astype(jnp.int32), block_tables, qb=qb,
        window=window, softcap=softcap, use_kernel=use_kernel,
        interpret=interpret,
    )
    return o.reshape(N, H, d)


def ssd(x, dt, A, Bm, Cm, h0=None, *, chunk=_ssd.DEFAULT_CHUNK, interpret=False):
    """Model-layout wrapper mirroring models.mamba.ssd_chunked.

    x: (B,S,nh,hd), dt: (B,S,nh) fp32, A: (nh,), Bm/Cm: (B,S,G,ds).
    Returns (y (B,S,nh,hd), hT (B,nh,hd,ds)).
    """
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // L

    xk = x.reshape(B, nc, L, nh, hd).transpose(0, 3, 1, 2, 4)
    dtf = dt.astype(jnp.float32)
    a = (dtf * A).reshape(B, nc, L, nh, 1).transpose(0, 3, 1, 2, 4)
    dtk = dtf.reshape(B, nc, L, nh, 1).transpose(0, 3, 1, 2, 4)
    Bk = Bm.reshape(B, nc, L, G, ds).transpose(0, 3, 1, 2, 4)
    Ck = Cm.reshape(B, nc, L, G, ds).transpose(0, 3, 1, 2, 4)
    if h0 is None:
        h0 = jnp.zeros((B, nh, hd, ds), jnp.float32)

    y, hT = _ssd.ssd_chunk_scan(xk, a, dtk, Bk, Ck, h0, interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(B, Sp, nh, hd)
    return y[:, :S], hT
