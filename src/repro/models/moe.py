"""Mixture-of-Experts channel mixer: top-k router + capacity-based dispatch.

Dispatch is GShard-style scatter/gather with a fixed per-expert capacity so
the compiled FLOPs scale with top_k (not n_experts) — this keeps the dry-run
cost_analysis honest about *active* compute, and the (E, C, d) expert batch
shards cleanly over the "model" (expert-parallel) mesh axis.

Serving takes ``moe_routed`` instead: dropless by construction, and only the
routed (row, expert) pairs reach the expert matmuls, grouped by expert.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard_act
from repro.models.layers import act_fn, dense_init, ffn, ffn_init

CAPACITY_FACTOR = 1.25


def moe_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, cfg.n_experts + 2)
    experts = [
        ffn_init(ks[i], cfg.d_model, cfg.moe_d_ff, glu=cfg.glu, dtype=dtype)
        for i in range(cfg.n_experts)
    ]
    p = {
        "router": dense_init(ks[-2], cfg.d_model, cfg.n_experts, std=0.02, dtype=dtype),
        "experts": jax.tree.map(lambda *xs: jnp.stack(xs), *experts),
    }
    if cfg.n_shared_experts:
        p["shared"] = ffn_init(ks[-1], cfg.d_model, cfg.shared_d_ff, glu=cfg.glu,
                               dtype=dtype)
    return p


def moe_routed(params, cfg: ModelConfig, x, valid=None, *, layer=None,
               use_kernel: bool = False, interpret: bool = False):
    """The routed experts of the serving step over packed rows ``x`` (N, d):
    each row goes to its top-k experts of the router's softmax over all
    ``n_experts``, whatever ``moe_capacity_factor`` says (no row is ever
    dropped), and only those N x top_k routed rows are computed.

    Under scope ``moe_route``: router, top-k, the tile-aligned layout of the
    (row, expert) pairs grouped by expert (``kernels.grouped_matmul``), the
    gather of their rows and the weighted sum back. Under ``moe_experts``:
    the SwiGLU as two grouped matmuls (gate and up together, then down),
    which stream each hit expert's weights once; the Pallas kernel with
    ``use_kernel`` / ``interpret``, else ``jax.lax.ragged_dot``. ``valid``
    (N,) marks real rows; padding rows join no group. With ``layer`` (a
    traced index) the experts' weights are stacked over layers and that
    layer's are used. The shared experts are the caller's. Returns (y (N,
    d), routed rows per expert (n_experts,) int32)."""
    from repro.kernels.grouped_matmul import TM, grouped_matmul, tile_layout, tiles_for

    N, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    with jax.named_scope("moe_route"):
        # router scores in float32 (as HF's MoEGate): a top-k boundary
        # rounded differently picks another expert
        logits = jnp.dot(x.astype(jnp.float32), params["router"]["w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        if cfg.norm_topk:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        expert = top_i.reshape(N * K)  # pairs in (row, k) order
        if valid is not None:  # padding rows join no group
            expert = jnp.where(jnp.repeat(valid, K), expert, E)
        tiles = tiles_for(N * K, E)
        dest, sizes, padded, tile_expert, n_tiles = tile_layout(expert, E, tiles)
        xs = jnp.zeros((tiles * TM, d), x.dtype).at[dest].set(
            jnp.repeat(x, K, axis=0), mode="drop")
    with jax.named_scope("moe_experts"):
        w = jax.tree.map(lambda a: a.astype(x.dtype), params["experts"])
        act = act_fn(cfg.act)
        run = functools.partial(grouped_matmul, tile_expert=tile_expert, n_tiles=n_tiles,
                                group_rows=padded, layer=layer, use_kernel=use_kernel,
                                interpret=interpret)
        if cfg.glu:
            h = run(xs, (w["gate"]["w"], w["up"]["w"]), act=act)
        else:
            h = act(run(xs, (w["up"]["w"],)))
        ys = run(h, (w["down"]["w"],))
    with jax.named_scope("moe_route"):
        keep = dest < tiles * TM
        yk = jnp.where(keep[:, None], ys[jnp.minimum(dest, tiles * TM - 1)], 0)
        y = jnp.sum(yk.astype(jnp.float32).reshape(N, K, d) * top_w[..., None], axis=1)
    return y.astype(x.dtype), sizes


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cf = cfg.moe_capacity_factor
    if cf >= cfg.n_experts:  # dropless: one expert could receive every token,
        return n_tokens      # but at most once each (top-k indices distinct)
    c = math.ceil(cfg.top_k * n_tokens / cfg.n_experts * cf)
    return max(4, -(-c // 4) * 4)  # >=4, multiple of 4


def moe_apply(params, cfg: ModelConfig, x):
    """x: (B, T, d) -> (y, aux_loss). Dropped-over-capacity tokens keep residual only.

    With an activation mesh installed (dry-run / launchers) dispatch runs as a
    shard_map: token routing is LOCAL per DP shard and each model shard
    computes only its own experts (EP), with a single psum("model") combine —
    no cross-device scatter, which XLA's SPMD partitioner handles badly.
    """
    from repro.distributed import ctx

    mesh = ctx.activation_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        return _moe_apply_sharded(params, cfg, x, mesh)
    return _moe_apply_local(params, cfg, x)


def _moe_apply_local(params, cfg: ModelConfig, x):
    B, T, d = x.shape
    N = B * T
    E, K = cfg.n_experts, cfg.top_k
    xf = x.reshape(N, d)

    logits = (xf @ params["router"]["w"].astype(xf.dtype)).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)  # (N, K)
    if cfg.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w.astype(xf.dtype)

    C = expert_capacity(N, cfg)

    # position of each (token, slot) within its expert's capacity buffer;
    # earlier slots get priority (GShard). Slots are processed one at a time
    # so the live set is (N, E), never (N, K, E).
    slot_pos_ks = []
    count = jnp.zeros((E,), jnp.int32)
    ce_frac = None  # slot-0 dispatch fraction for the aux loss
    for k in range(K):
        oh = jax.nn.one_hot(top_i[:, k], E, dtype=jnp.int32)  # (N, E)
        pos_k = count[None, :] + jnp.cumsum(oh, axis=0) - oh
        slot_pos_ks.append(jnp.sum(pos_k * oh, axis=-1))  # (N,)
        csum = jnp.sum(oh, axis=0)
        if k == 0:
            ce_frac = csum.astype(jnp.float32) / max(N, 1)
        count = count + csum
    slot_pos = jnp.stack(slot_pos_ks, axis=1)  # (N, K)
    keep = slot_pos < C  # (N, K)
    # per-expert buffers get one overflow row (index C) that is written by
    # dropped tokens and never read back — keeps the buffer EP-shardable
    flat_idx = top_i * (C + 1) + jnp.minimum(slot_pos, C)  # (N, K)

    buf = jnp.zeros((E * (C + 1), d), xf.dtype)
    src = jnp.repeat(xf[:, None, :], K, axis=1).reshape(N * K, d)
    buf = buf.at[flat_idx.reshape(-1)].set(src)  # duplicate writes identical per token
    xe = shard_act(buf.reshape(E, C + 1, d), "model", None, None)  # EP layout

    ye = jax.vmap(lambda p, h: ffn(p, h, cfg.act, cfg.glu))(params["experts"], xe)
    ye = shard_act(ye, "model", None, None)
    ybuf = ye.reshape(E * (C + 1), d)

    gathered = ybuf[flat_idx.reshape(-1)].reshape(N, K, d)
    w = (top_w * keep.astype(top_w.dtype))[..., None]
    y = jnp.sum(gathered * w, axis=1)

    if cfg.n_shared_experts:
        y = y + ffn(params["shared"], xf, cfg.act, cfg.glu)

    # switch-transformer load-balance aux loss: E * sum(mean_prob * dispatch_frac)
    me = jnp.mean(probs, axis=0)  # (E,)
    aux = E * jnp.sum(me * ce_frac)
    return y.reshape(B, T, d), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (dry-run / production meshes)
# ---------------------------------------------------------------------------


def _moe_apply_sharded(params, cfg: ModelConfig, x, mesh):
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import dp_axes

    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tp = mesh.shape["model"]
    if E % tp != 0:
        return _moe_apply_local(params, cfg, x)
    b_spec = dp if B % dp_size == 0 else None
    E_loc = E // tp

    routed = {"router": params["router"], "experts": params["experts"]}
    specs_params = {
        "router": jax.tree.map(lambda _: P(), routed["router"]),
        "experts": jax.tree.map(
            lambda l: P(*(["model"] + [None] * (len(l.shape) - 1))), routed["experts"]
        ),
    }

    def body(p, x_loc):
        Bl, Tl, _ = x_loc.shape
        N = Bl * Tl
        xf = x_loc.reshape(N, d)
        logits = (xf @ p["router"]["w"].astype(xf.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, K)
        if cfg.norm_topk:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        top_w = top_w.astype(xf.dtype)

        C = expert_capacity(N, cfg)
        slot_pos_ks, count, ce_frac = [], jnp.zeros((E,), jnp.int32), None
        for k in range(K):
            oh = jax.nn.one_hot(top_i[:, k], E, dtype=jnp.int32)
            pos_k = count[None, :] + jnp.cumsum(oh, axis=0) - oh
            slot_pos_ks.append(jnp.sum(pos_k * oh, axis=-1))
            csum = jnp.sum(oh, axis=0)
            if k == 0:
                ce_frac = csum.astype(jnp.float32) / max(N, 1)
            count = count + csum
        slot_pos = jnp.stack(slot_pos_ks, axis=1)
        keep = slot_pos < C
        flat_idx = top_i * (C + 1) + jnp.minimum(slot_pos, C)

        buf = jnp.zeros((E * (C + 1), d), xf.dtype)
        src = jnp.repeat(xf[:, None, :], K, axis=1).reshape(N * K, d)
        buf = buf.at[flat_idx.reshape(-1)].set(src)

        # my experts only (tokens are replicated over "model")
        m_idx = jax.lax.axis_index("model")
        xe = jax.lax.dynamic_slice_in_dim(
            buf.reshape(E, C + 1, d), m_idx * E_loc, E_loc, axis=0
        )
        ye = jax.vmap(lambda pe, h: ffn(pe, h, cfg.act, cfg.glu))(p["experts"], xe)
        ybuf = jnp.zeros((E, C + 1, d), ye.dtype)
        ybuf = jax.lax.dynamic_update_slice_in_dim(ybuf, ye, m_idx * E_loc, axis=0)

        gathered = ybuf.reshape(E * (C + 1), d)[flat_idx.reshape(-1)].reshape(N, K, d)
        w = (top_w * keep.astype(top_w.dtype))[..., None]
        y = jax.lax.psum(jnp.sum(gathered * w, axis=1), "model")

        # aux loss from GLOBAL statistics: pmean the factors, then the product
        me = jnp.mean(probs, axis=0)
        if b_spec is not None:
            me = jax.lax.pmean(me, dp)
            ce_frac = jax.lax.pmean(ce_frac, dp)
        aux = E * jnp.sum(me * ce_frac)
        return y.reshape(Bl, Tl, d), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(specs_params, P(b_spec, None, None)),
        out_specs=(P(b_spec, None, None), P()),
        check_vma=False,
    )(routed, x)

    if cfg.n_shared_experts:
        y = y + ffn(params["shared"], x.reshape(-1, d), cfg.act, cfg.glu).reshape(B, T, d)
    return y, aux
