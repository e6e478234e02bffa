"""Pure-jnp oracles for every kernel. Tests assert_allclose kernel vs these."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


def flash_attention_ref(
    q, k, v, *, causal=True, window: Optional[int] = None, softcap: Optional[float] = None
):
    """q: (B,H,Sq,d); k/v: (B,KV,Sk,d) -> (B,H,Sq,d). fp32 softmax."""
    B, H, Sq, d = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgtd,bksd->bkgts", qf, kf) / d**0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bksd->bkgtd", p, vf)
    return o.reshape(B, H, Sq, d).astype(q.dtype)


def decode_attention_ref(
    q, k, v, lengths, *, window: Optional[int] = None, softcap: Optional[float] = None
):
    """q: (B,KV,G,d); k/v: (B,KV,S,d); lengths (B,) -> (B,KV,G,d)."""
    B, KV, G, d = q.shape
    S = k.shape[2]
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32), k.astype(jnp.float32)) / d**0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = jnp.arange(S)[None, :]
    mask = k_pos < lengths[:, None]  # (B, S)
    if window is not None:
        mask &= k_pos > (lengths[:, None] - 1) - window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_attention_ref(
    q, k_pages, v_pages, lengths, block_tables,
    *, window: Optional[int] = None, softcap: Optional[float] = None,
):
    """q: (N,KV,G,d); k/v_pages: (P,KV,page,d) head-major pool; lengths
    (N,); block_tables (N,nb) -> (N,KV,G,d).

    Gathers each row's pages in block-table order (logical key position
    ib*page + offset), masks keys at/above the row's length, fp32 softmax.
    The last valid page may be partially filled; entries past
    ceil(length/page) are never read into the result (fully masked)."""
    N, KV, G, d = q.shape
    page = k_pages.shape[2]
    nb = block_tables.shape[1]
    S = nb * page

    def rows(pages):  # (N, nb, KV, page, d) -> (N, KV, nb*page, d)
        return (pages[block_tables].transpose(0, 2, 1, 3, 4)
                .reshape(N, KV, S, d).astype(jnp.float32))

    kc, vc = rows(k_pages), rows(v_pages)
    s = jnp.einsum("nkgd,nksd->nkgs", q.astype(jnp.float32), kc) / d**0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = jnp.arange(S)[None, :]
    mask = k_pos < lengths[:, None]  # (N, S)
    if window is not None:
        mask &= k_pos > (lengths[:, None] - 1) - window  # query pos = length-1
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nkgs,nksd->nkgd", p, vc).astype(q.dtype)


def mixed_attention_ref(
    q, k_pages, v_pages, cu_q_lens, kv_lens, block_tables,
    *, window: Optional[int] = None, softcap: Optional[float] = None,
):
    """Mixed-batch (unified prefill+decode) oracle by per-token expansion.

    q: (N,KV,G,d) flat packed rows; cu_q_lens: (S+1,) row offsets of each
    segment; kv_lens: (S,) total keys each segment's *last* row attends
    (= context length after the chunk); block_tables: (S,nb) per-segment page
    ids. Row j of segment s (a prefill-chunk token, or the single row of a
    decode segment) attends ``kv_lens[s] - q_len[s] + j + 1`` keys — exactly
    the intra-chunk causal mask the Pallas kernel applies — so expanding to
    per-row lengths and delegating to :func:`paged_attention_ref` is the
    mixed kernel's ground truth by construction. Rows at/after
    ``cu_q_lens[-1]`` are padding: they read (valid) garbage and are
    discarded by the caller, like every packed padding row.
    """
    seg, lengths = _row_lengths(cu_q_lens, kv_lens, q.shape[0])
    return paged_attention_ref(
        q, k_pages, v_pages, lengths, block_tables[seg],
        window=window, softcap=softcap,
    )


def _row_lengths(cu_q_lens, kv_lens, N: int):
    """(segment (N,), keys attended (N,)) of each packed row: row j of a
    segment attends ``kv_len - q_len + j + 1`` keys (at least 1)."""
    S = kv_lens.shape[0]
    cu = cu_q_lens.astype(jnp.int32)
    row = jnp.arange(N, dtype=jnp.int32)
    seg = jnp.clip(jnp.searchsorted(cu, row, side="right") - 1, 0, S - 1)
    q_len = cu[seg + 1] - cu[seg]
    j = row - cu[seg]
    return seg, jnp.maximum(kv_lens[seg] - q_len + j + 1, 1)


def mixed_mla_attention_ref(q_lat, q_rope, c_pages, r_pages, cu_q_lens, kv_lens,
                            block_tables, *, scale: float):
    """Latent (MLA) mixed-batch oracle by per-token expansion, as
    :func:`mixed_attention_ref`: absorbed queries q_lat (N, H, L) and
    q_rope (N, H, rope); c_pages (P, page, >= L) and r_pages (P, page,
    >= rope) holding the latent and rope keys in their leading lanes ->
    (N, H, L), each row's softmax over its own keys (latent and rope
    scores) of the latent values, fp32."""
    N, _, L = q_lat.shape
    R = q_rope.shape[-1]
    page = c_pages.shape[1]
    seg, lengths = _row_lengths(cu_q_lens, kv_lens, N)
    tabs = block_tables[seg]  # (N, nb): each row's segment's pages
    S = tabs.shape[1] * page
    c = c_pages[tabs].reshape(N, S, -1)[..., :L].astype(jnp.float32)
    kr = r_pages[tabs].reshape(N, S, -1)[..., :R].astype(jnp.float32)
    s = (jnp.einsum("nhl,nsl->nhs", q_lat.astype(jnp.float32), c)
         + jnp.einsum("nhr,nsr->nhs", q_rope.astype(jnp.float32), kr)) * scale
    s = jnp.where((jnp.arange(S)[None, :] < lengths[:, None])[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhs,nsl->nhl", p, c).astype(q_lat.dtype)


def ssd_ref(x, dt, A, Bm, Cm, h0=None):
    """Sequential (exact) SSD recurrence oracle.

    x: (B,S,nh,hd), dt: (B,S,nh) fp32, A: (nh,), Bm/Cm: (B,S,G,ds).
    Returns y (B,S,nh,hd), hT (B,nh,hd,ds).
    """
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    rep = nh // G
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)  # (B,S,nh,ds)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(h, t):
        a = jnp.exp(dtf[:, t] * A)  # (B,nh)
        upd = jnp.einsum("bh,bhd,bhs->bhds", dtf[:, t], xf[:, t], Bh[:, t])
        h = h * a[..., None, None] + upd
        y = jnp.einsum("bhds,bhs->bhd", h, Ch[:, t])
        return h, y

    h = jnp.zeros((B, nh, hd, ds), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    ys = []
    for t in range(S):
        h, y = step(h, t)
        ys.append(y)
    y = jnp.stack(ys, axis=1)
    return y.astype(x.dtype), h
