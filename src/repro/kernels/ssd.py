"""Mamba2 SSD (state-space duality) chunk-scan Pallas kernel.

Grid (B, nh, nc) with the chunk dim sequential: the inter-chunk SSM state
h (hd, ds) is carried in VMEM scratch while per-chunk X/B/C blocks stream
from HBM — the same compute/transfer overlap pattern as the attention
kernels. Each chunk does the quadratic-in-L intra-chunk term on the MXU plus
the rank-ds inter-chunk correction, i.e. the sub-quadratic SSD algorithm
used for the long_500k cells.

Inputs (pre-arranged by ops.ssd):
  x  : (B, nh, nc, L, hd)
  a  : (B, nh, nc, L, 1)   decay increments dt*A (fp32, negative)
  dt : (B, nh, nc, L, 1)   softplus'd step sizes (fp32)
  Bm : (B, G,  nc, L, ds)
  Cm : (B, G,  nc, L, ds)
  h0 : (B, nh, hd, ds)     initial state (chunked-prefill handoff)
Outputs:
  y  : (B, nh, nc, L, hd)
  hT : (B, nh, hd, ds)     final state
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref, h_ref, *, L):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (L, hd)
    a = a_ref[0, 0, 0].astype(jnp.float32)  # (L, 1)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (L, 1)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)  # (L, ds)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)  # (L, ds)
    h = h_ref[...]  # (hd, ds)

    cum = jnp.cumsum(a, axis=0)  # (L, 1)

    # intra-chunk: w[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, j <= i
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    dec = jnp.exp(cum - cum.reshape(1, L))  # cum_i - cum_j
    w = jnp.where(ii >= jj, CB * dec, 0.0) * dt.reshape(1, L)
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, hd)

    # inter-chunk: y_i += exp(cum_i) * C_i . h_start
    y = y + jax.lax.dot_general(Cm * jnp.exp(cum), h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: h' = exp(cum_L) h + sum_j exp(cum_L - cum_j) dt_j x_j^T B_j
    total = cum[L - 1 : L, :]  # (1, 1)
    wj = jnp.exp(total - cum) * dt  # (L, 1)
    h_new = h * jnp.exp(total)[0, 0] + jax.lax.dot_general(
        x, Bm * wj, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (hd, ds)
    h_ref[...] = h_new

    @pl.when(ic == nc - 1)
    def _final():
        hT_ref[0, 0] = h_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_scan(x, a, dt, Bm, Cm, h0, *, interpret: bool = False):
    B, nh, nc, L, hd = x.shape
    G, ds = Bm.shape[1], Bm.shape[4]
    rep = nh // G
    grid = (B, nh, nc)

    kernel = functools.partial(_ssd_kernel, L=L)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, hd), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, ds), lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, ds), lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, hd), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_chunk_scan",
    )(x, a, dt, Bm, Cm, h0)
    return y, hT
