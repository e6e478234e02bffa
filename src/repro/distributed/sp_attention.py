"""Sequence-parallel (SP) decode attention: flash-decoding over sharded KV.

For batch-1 long-context decode (the long_500k cells) the data axis cannot
carry batch, so it carries the KV *sequence* instead. Each shard computes
partial attention over its KV slice with a local running softmax, then the
shards combine with a renormalizing psum:

    m = pmax(m_i);  l = psum(l_i * e^{m_i - m});  o = psum(o_i * e^{m_i - m}) / l

One collective round (pmax + 2 psums) regardless of context length — the
same combine used by flash-decoding on GPUs, mapped to a TPU mesh axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1.0e30


def sp_decode_attention(
    q,  # (B, 1, H, d)
    k,  # (B, S, KV, d) — S sharded over `axis`
    v,
    lengths,  # (B,) valid KV tokens
    mesh: Mesh,
    axis: str = "data",
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    batch_axes=None,  # mesh axes carrying the batch dim (decode_32k: data)
):
    B_g, T, H, d = q.shape
    assert T == 1
    S = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    n_shards = mesh.shape[axis]
    assert S % n_shards == 0
    s_loc = S // n_shards
    scale = 1.0 / d**0.5
    if batch_axes:
        b_size = 1
        for a in batch_axes:
            b_size *= mesh.shape[a]
        b_ax = tuple(batch_axes) if B_g % b_size == 0 else None
    else:
        b_ax = None
    B = B_g // (b_size if b_ax else 1)

    def body(q, k, v, lengths):
        idx = jax.lax.axis_index(axis)
        offset = idx * s_loc
        qg = q[:, 0].reshape(B, KV, G, d)
        s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = offset + jnp.arange(s_loc)
        ok = k_pos[None, :] < lengths[:, None]  # (B, s_loc)
        if window is not None:
            ok &= k_pos[None, :] > (lengths[:, None] - 1) - window
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        m_i = jnp.max(s, axis=-1)  # (B,KV,G)
        p = jnp.exp(s - m_i[..., None])
        p = jnp.where(ok[:, None, None, :], p, 0.0)
        l_i = jnp.sum(p, axis=-1)
        o_i = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))

        m = jax.lax.pmax(m_i, axis)
        scale_i = jnp.exp(m_i - m)  # o_i is already p-weighted: rescale only
        l = jax.lax.psum(l_i * scale_i, axis)
        o = jax.lax.psum(o_i * scale_i[..., None], axis) / jnp.maximum(l, 1e-37)[..., None]
        return o.reshape(B, 1, H, d).astype(q.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(b_ax, None, None, None), P(b_ax, axis, None, None),
                  P(b_ax, axis, None, None), P(b_ax)),
        out_specs=P(b_ax, None, None, None),
        check_vma=False,
    )(q, k, v, lengths)
