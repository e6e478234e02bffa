"""GPipe-style pipeline parallelism over a mesh axis (optional PP).

The pod axis can run as a pipeline instead of folding into DP: each pod rank
owns a contiguous block of layers (one stage); microbatches stream through
with collective_permute hops between neighbors. Bubble fraction is
(P-1)/(M+P-1) — the launcher exposes `pipeline=True` for very-deep archs;
the 40 baseline cells use DP-over-pods (better roofline at these sizes, see
EXPERIMENTS.md).

`pipeline_apply` is deliberately generic: stage_fn is any (stage_params, x)
-> y; params arrive stacked over stages and sharded P(axis, ...).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x (mb, ...)) -> (mb, ...)
    stage_params: Any,  # leaves stacked over stages: (P_stages, ...)
    x,  # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
):
    """Returns (M, mb, ...) outputs after all stages, GPipe schedule."""
    n_stages = mesh.shape[axis]
    M = x.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_local, x_local):
        # params_local: this stage's params (leading stage dim stripped to 1)
        params_local = jax.tree.map(lambda l: l[0], params_local)
        stage = jax.lax.axis_index(axis)
        ticks = M + n_stages - 1
        buf = jnp.zeros_like(x_local[0])  # current activation on this stage
        outs = jnp.zeros_like(x_local)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range); others use buf
            feed = jnp.where(
                t < M, x_local[jnp.minimum(t, M - 1)], jnp.zeros_like(buf)
            )
            h_in = jnp.where(stage == 0, feed, buf)
            h_out = stage_fn(params_local, h_in)
            # pass to the next stage
            nxt = jax.lax.ppermute(h_out, axis, perm)
            # last stage emits microbatch (t - (n_stages - 1))
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (out_idx < M) & (stage == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # outputs live on the last stage; broadcast via psum of masked copies
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    in_param_specs = jax.tree.map(
        lambda l: P(*([axis] + [None] * (len(l.shape) - 1))), stage_params
    )
    other = tuple(a for a in mesh.axis_names if a != axis)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(in_param_specs, P(*([None] * x.ndim))),
        out_specs=P(*([None] * x.ndim)),
        check_vma=False,
    )(stage_params, x)
