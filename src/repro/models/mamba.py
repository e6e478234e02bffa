"""State-space sequence mixers: Mamba2 (SSD) and Mamba1 (Jamba's mixer).

Training/prefill uses the chunked SSD algorithm (sub-quadratic: O(S·L) intra-
chunk + O(S·d_state) inter-chunk recurrence); decode is an O(1) recurrent
state update — there is no KV cache, which is why the paper's KV-prefetch is
inapplicable to this family (DESIGN.md §4).

State caches:
  mamba2: {"conv": (B, W-1, d_conv_ch), "ssm": (B, nh, hd, ds)}
  mamba1: {"conv": (B, W-1, d_in),      "ssm": (B, d_in, ds)}
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense, dense_init, rms_norm, truncated_normal

CHUNK = 128


# ---------------------------------------------------------------------------
# causal depthwise conv1d (width W, channels-last)
# ---------------------------------------------------------------------------


def causal_conv(x, w, b, state=None):
    """x: (B,S,C), w: (W,C), b: (C,). state: (B,W-1,C) carried inputs or None.

    Returns (y, new_state) where new_state holds the last W-1 inputs.
    """
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, S+W-1, C)
    y = sum(xp[:, i : i + x.shape[1], :] * w[i].astype(x.dtype) for i in range(W))
    y = y + b.astype(x.dtype)
    new_state = xp[:, -(W - 1) :, :]
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------


def _m2_dims(cfg: ModelConfig):
    d_in = cfg.m_expand * cfg.d_model
    nh = d_in // cfg.m_headdim
    return d_in, nh, cfg.m_headdim, cfg.m_n_groups, cfg.m_d_state


def mamba2_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    d_in, nh, hd, G, ds = _m2_dims(cfg)
    conv_ch = d_in + 2 * G * ds
    ks = jax.random.split(rng, 4)
    dt = jnp.exp(
        jax.random.uniform(ks[2], (nh,)) * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    return {
        "in_proj": dense_init(ks[0], cfg.d_model, 2 * d_in + 2 * G * ds + nh, dtype=dtype),
        "conv_w": truncated_normal(ks[1], (cfg.m_conv, conv_ch), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(dt)),  # softplus^-1
        "norm_scale": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[3], d_in, cfg.d_model, dtype=dtype),
    }


def ssd_chunked(x, dt, A, B_, C_, chunk=CHUNK, h0=None):
    """Chunked state-space-duality scan (pure-jnp oracle; kernel mirrors this).

    x: (B,S,nh,hd) dt: (B,S,nh) A: (nh,) B_,C_: (B,S,G,ds)
    Returns y: (B,S,nh,hd), final state (B,nh,hd,ds).
    """
    Bsz, S, nh, hd = x.shape
    G, ds = B_.shape[2], B_.shape[3]
    rep = nh // G
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    xc = x.reshape(Bsz, nc, L, nh, hd)
    dtc = dt.reshape(Bsz, nc, L, nh).astype(jnp.float32)
    Bc = B_.reshape(Bsz, nc, L, G, ds)
    Cc = C_.reshape(Bsz, nc, L, G, ds)

    a = dtc * A  # (B,nc,L,nh) negative decay increments
    cum = jnp.cumsum(a, axis=2)  # within-chunk cumulative log-decay

    # ---- intra-chunk (quadratic in L only) --------------------------------
    CB = jnp.einsum("bclgs,bcmgs->bcglm", Cc, Bc)  # (B,nc,G,L,L)
    CB = jnp.repeat(CB, rep, axis=2)  # (B,nc,nh,L,L)
    # decay(i,j) = exp(cum_i - cum_j) for i >= j
    ci = cum.transpose(0, 1, 3, 2)  # (B,nc,nh,L)
    dec = jnp.exp(ci[..., :, None] - ci[..., None, :])  # (B,nc,nh,L,L)
    mask = jnp.tril(jnp.ones((L, L), bool))
    w = jnp.where(mask, CB.astype(jnp.float32) * dec, 0.0)
    w = w * dtc.transpose(0, 1, 3, 2)[..., None, :]  # × dt_j
    y_intra = jnp.einsum("bchlm,bcmhd->bclhd", w.astype(x.dtype), xc)

    # ---- chunk summary states --------------------------------------------
    # S_c = sum_j exp(cum_L - cum_j) dt_j B_j x_j^T  -> (B,nc,nh,hd,ds)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,L,nh)
    wj = (decay_to_end * dtc).astype(x.dtype)
    Bhead = jnp.repeat(Bc, rep, axis=3)  # (B,nc,L,nh,ds)
    Chead = jnp.repeat(Cc, rep, axis=3)
    Sc = jnp.einsum("bclh,bclhd,bclhs->bchds", wj, xc, Bhead)  # (B,nc,nh,hd,ds)

    # ---- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,nh) total decay of a chunk

    def step(h, inp):
        sc, cd = inp  # (B,nh,hd,ds), (B,nh)
        h_new = h * cd[..., None, None].astype(h.dtype) + sc
        return h_new, h  # emit state at chunk START

    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, x.shape[3], ds), jnp.float32)
    hT, h_starts = jax.lax.scan(
        step,
        h0,
        (Sc.transpose(1, 0, 2, 3, 4).astype(jnp.float32), chunk_decay.transpose(1, 0, 2)),
    )
    h_starts = h_starts.transpose(1, 0, 2, 3, 4)  # (B,nc,nh,hd,ds)

    # Y_inter[i] = exp(cum_i) * C_i . h_chunk_start
    y_inter = jnp.einsum(
        "bclhs,bchds->bclhd", (Chead.astype(jnp.float32) * jnp.exp(cum)[..., None]), h_starts
    )
    y = y_intra + y_inter.astype(x.dtype)
    return y.reshape(Bsz, S, nh, hd), hT


def mamba2_apply(params, cfg: ModelConfig, u, *, state=None, want_state=False):
    """u: (B,S,d). state: {"conv","ssm"} or None. Returns (y, new_state|None)."""
    d_in, nh, hd, G, ds = _m2_dims(cfg)
    B, S, _ = u.shape
    zxbcdt = dense(params["in_proj"], u)
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in : 2 * d_in + 2 * G * ds]
    dt_raw = zxbcdt[..., 2 * d_in + 2 * G * ds :]  # (B,S,nh)

    conv_state = state["conv"] if state is not None else None
    xBC, new_conv = causal_conv(xBC, params["conv_w"], params["conv_b"], conv_state)
    xBC = jax.nn.silu(xBC)
    x = xBC[..., :d_in].reshape(B, S, nh, hd)
    B_ = xBC[..., d_in : d_in + G * ds].reshape(B, S, G, ds)
    C_ = xBC[..., d_in + G * ds :].reshape(B, S, G, ds)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(params["A_log"])  # (nh,)

    h0 = state["ssm"].astype(jnp.float32) if state is not None else None
    if S == 1 and state is not None:
        # O(1) recurrent decode step
        a = jnp.exp(dt[:, 0] * A)  # (B,nh)
        Bh = jnp.repeat(B_[:, 0], nh // G, axis=1)  # (B,nh,ds)
        Ch = jnp.repeat(C_[:, 0], nh // G, axis=1)
        dBx = jnp.einsum("bh,bhd,bhs->bhds", dt[:, 0], x[:, 0].astype(jnp.float32), Bh.astype(jnp.float32))
        hT = h0 * a[..., None, None] + dBx
        y = jnp.einsum("bhds,bhs->bhd", hT, Ch.astype(jnp.float32))[:, None]  # (B,1,nh,hd)
        y = y.astype(u.dtype)
    else:
        pad = (-S) % CHUNK
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
            C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, hT = ssd_chunked(x, dt, A, B_, C_, h0=h0)
        y = y[:, :S]
        x = x[:, :S]

    y = y + params["D"].astype(u.dtype)[:, None] * x
    y = y.reshape(B, S, d_in)
    y = y * jax.nn.silu(z)
    y = rms_norm({"scale": params["norm_scale"]}, y, cfg.norm_eps)
    out = dense(params["out_proj"], y)
    new_state = {"conv": new_conv, "ssm": hT} if (state is not None or want_state) else None
    return out, new_state


def mamba2_state_init(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d_in, nh, hd, G, ds = _m2_dims(cfg)
    conv_ch = d_in + 2 * G * ds
    return {
        "conv": jnp.zeros((batch, cfg.m_conv - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, nh, hd, ds), jnp.float32),
    }


# ---------------------------------------------------------------------------
# Mamba1 (Jamba's mixer)
# ---------------------------------------------------------------------------


def _m1_dims(cfg: ModelConfig):
    d_in = cfg.m_expand * cfg.d_model
    dt_rank = math.ceil(cfg.d_model / 16)
    return d_in, dt_rank, cfg.m_d_state_m1


def mamba1_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    d_in, dt_rank, ds = _m1_dims(cfg)
    ks = jax.random.split(rng, 5)
    A = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (d_in, 1))
    return {
        "in_proj": dense_init(ks[0], cfg.d_model, 2 * d_in, dtype=dtype),
        "conv_w": truncated_normal(ks[1], (cfg.m_conv, d_in), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((d_in,), jnp.float32),
        "x_proj": dense_init(ks[2], d_in, dt_rank + 2 * ds, dtype=dtype),
        "dt_proj": dense_init(ks[3], dt_rank, d_in, bias=True, dtype=dtype),
        "A_log": jnp.log(A),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[4], d_in, cfg.d_model, dtype=dtype),
    }


def _m1_scan_chunked(a, b, C_, h0, chunk=CHUNK):
    """Linear recurrence h_t = a_t h_{t-1} + b_t, y_t = h_t . C_t.

    a, b: (B,S,d_in,ds) fp32; C_: (B,S,ds). Chunked to bound live memory.
    """
    Bsz, S, d_in, ds = a.shape
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    ac = a.reshape(Bsz, nc, L, d_in, ds).transpose(1, 0, 2, 3, 4)
    bc = b.reshape(Bsz, nc, L, d_in, ds).transpose(1, 0, 2, 3, 4)
    Cc = C_.reshape(Bsz, nc, L, ds).transpose(1, 0, 2, 3)

    def assoc(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, inp):
        a_, b_, c_ = inp  # (B,L,d_in,ds) ×2, (B,L,ds)
        aa, bb = jax.lax.associative_scan(assoc, (a_, b_), axis=1)
        h_all = aa * h[:, None] + bb  # (B,L,d_in,ds)
        y = jnp.einsum("blds,bls->bld", h_all, c_)
        return h_all[:, -1], y

    hT, ys = jax.lax.scan(chunk_step, h0, (ac, bc, Cc))
    y = ys.transpose(1, 0, 2, 3).reshape(Bsz, S, d_in)
    return y, hT


def mamba1_apply(params, cfg: ModelConfig, u, *, state=None, want_state=False):
    d_in, dt_rank, ds = _m1_dims(cfg)
    B, S, _ = u.shape
    xz = dense(params["in_proj"], u)
    x, z = xz[..., :d_in], xz[..., d_in:]
    conv_state = state["conv"] if state is not None else None
    x, new_conv = causal_conv(x, params["conv_w"], params["conv_b"], conv_state)
    x = jax.nn.silu(x)

    xdbc = dense(params["x_proj"], x)
    dt = jax.nn.softplus(dense(params["dt_proj"], xdbc[..., :dt_rank]).astype(jnp.float32))
    B_ = xdbc[..., dt_rank : dt_rank + ds].astype(jnp.float32)  # (B,S,ds)
    C_ = xdbc[..., dt_rank + ds :].astype(jnp.float32)

    A = -jnp.exp(params["A_log"])  # (d_in, ds)
    x32 = x.astype(jnp.float32)
    a = jnp.exp(dt[..., None] * A)  # (B,S,d_in,ds)
    b = (dt * x32)[..., None] * B_[:, :, None, :]  # (B,S,d_in,ds)

    h0 = state["ssm"].astype(jnp.float32) if state is not None else jnp.zeros((B, d_in, ds), jnp.float32)
    if S == 1 and state is not None:
        hT = a[:, 0] * h0 + b[:, 0]
        y = jnp.einsum("bds,bs->bd", hT, C_[:, 0])[:, None]
    else:
        pad = (-S) % CHUNK
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
            b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
            C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
        y, hT = _m1_scan_chunked(a, b, C_, h0)
        y = y[:, :S]

    y = y.astype(u.dtype) + params["D"].astype(u.dtype) * x
    y = y * jax.nn.silu(z)
    out = dense(params["out_proj"], y)
    new_state = {"conv": new_conv, "ssm": hT} if (state is not None or want_state) else None
    return out, new_state


def mamba1_state_init(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d_in, dt_rank, ds = _m1_dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.m_conv - 1, d_in), dtype),
        "ssm": jnp.zeros((batch, d_in, ds), jnp.float32),
    }
