"""Packed forward step: prefill-chunk tokens + decode tokens in ONE call.

This is the paper's packing made real in JAX: the step takes a flat token
set — one token per decoding request plus a chunk of a prefilling request —
and runs every linear/FFN/MoE op over the packed (N, d) token matrix, so
model weights stream from HBM once per step (the compute-bound conversion of
decode linear ops, §III). Attention is per-token over the owning request's
KV-cache row: all N tokens first scatter their K/V into (slot, position),
then each attends under the mask k_pos <= position — which makes intra-chunk
causality and cross-request isolation hold by construction.

Works for attention-family architectures (incl. MLA). SSM/hybrid mixers need
contiguous per-segment scans, so those archs use the engine's two-call mode
(their decode is state-recurrent and not KV-bound — DESIGN.md §4).

Two attention realizations:
  * dense gather (``paged=None``) — KV lives in a dense (slot, max_len) slot
    cache; writes scatter at (slot, position) and `cache[slots]` pulls every
    row's full padded KV extent: O(N * S_max) bytes/FLOPs regardless of real
    lengths. Kept as reference/fallback.
  * ragged paged (``paged`` given) — KV lives in a *physical page pool*
    (n_pages, KV, page_size, d) (MLA latents: (n_pages, page_size, L)),
    stacked over the scanned layers, which the scan neither slices out nor
    writes back: the step writes its pages in place and the kernels read
    their layer by index.
    both reads AND this step's writes route through the engine's
    block-table mirror, which carries the allocator's real (arbitrary,
    non-contiguous) page ids. A row's token at position p
    scatters into page ``table[slot, p // page]`` offset ``p % page``, and
    attention reads only the pages the row's table names, bounded to the
    live context (tables arrive sliced to ``nb = ceil(max_live_len /
    page_size)`` columns) and to the row's own position: O(N * len). On TPU
    this is kernels/paged_attention.py (out-of-range pages are skipped per
    row); on CPU the jnp oracle gathers the same bounded page set.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import moe
from repro.models.attention import NEG_INF, mla_scale, softcap
from repro.models.layers import apply_rope, dense, ffn, rms_norm
from repro.models.model import Model


# the step's named scopes (``jax.named_scope``): every operation of the
# compiled step carries one in its metadata, so a profiler trace's device
# time can be summed by part of the step (``Engine.step_op_scopes``).
# ``kv_pool`` is the KV pool's movement: the step's K/V written into the
# stacked pool in place (a head-major GQA pool's touched pages gathered,
# merged with the step's rows and scattered back whole; a latent pool's
# rows scattered), no layer's pool copied out or back.
# ``moe_route`` and ``moe_experts`` are a MoE layer's routing (router,
# top-k, the routed rows' tile-aligned layout, their gather and weighted
# sum back) and its grouped expert matmuls; the shared experts and dense
# FFNs stay under ``mlp``.
STEP_SCOPES = ("embed", "qkv", "kv_pool", "attention", "o_proj", "mlp",
               "moe_route", "moe_experts", "lm_head")


def supports_packed(cfg: ModelConfig) -> bool:
    return (not cfg.encdec) and all(s.mixer == "attn" for s in cfg.layer_specs)


@dataclasses.dataclass
class PagedView:
    """Ragged paged-attention inputs for one packed step over a *physical*
    page pool.

    The cache arrays are head-major (n_pages, KV, page_size, d) pools
    (MLA latents: (n_pages, page_size, L), L padded to whole lane tiles) —
    there is no dense slot axis.
    ``block_tables`` is the engine's device mirror of the allocator's block
    tables — one row per scheduler slot (incl. the scratch
    slot), carrying the allocator's **actual** page ids, already sliced to
    ``nb`` columns where ``nb * page_size`` covers the longest live context
    this step. Dead entries point at the scratch page, so every id is a
    valid pool index for the jnp oracles, which gather whole tables; the
    kernels never read them.

    The segment layout (``cu_q_lens`` / ``kv_lens`` / ``seg_slots``) carries
    the step's mixed batch: decode rows first (one 1-token segment each),
    then one segment per prefill chunk, padding segments zero-length against
    the scratch slot. ``q_block`` is the static pow2 bucket of the longest
    segment — the Pallas q-block row count, part of the jit cache key."""

    block_tables: jax.Array  # (n_slots+1, nb) int32 physical page ids
    page_size: int
    use_kernel: bool = False  # Pallas kernel (TPU) vs jnp oracle (CPU)
    interpret: bool = False
    cu_q_lens: Optional[jax.Array] = None  # (S+1,) int32 packed-row offsets
    kv_lens: Optional[jax.Array] = None  # (S,) int32 keys per segment
    seg_slots: Optional[jax.Array] = None  # (S,) int32 owning slot per segment
    q_block: int = 1  # static pow2 q-block rows for the mixed kernel

    def seg_tables(self) -> jax.Array:
        """Per-segment tables: each mixed-batch segment reads through the
        table of the slot that owns it."""
        return self.block_tables[self.seg_slots]

    def scatter(self, pool: jax.Array, slots, positions, values,
                layer=None) -> jax.Array:
        """Write each row's new K/V through the block table, into layer
        ``layer`` of a pool stacked over layers (an unstacked pool without
        it): token at logical position p of slot s lands in physical page
        ``table[s, p // page]`` at offset ``p % page``. The scheduler grew
        the tables at plan time, so the target pages always exist.

        GQA values (N, KV, d) go into a head-major (L, P, KV, page, d)
        pool as whole pages: a page of one head is one (page, d) tile, and
        a row scatter into it makes XLA relayout the whole stack. So the N
        touched pages are gathered, each takes every row the step writes
        into it (rows sharing a page thus carry the same page, and of rows
        sharing a position the last wins), and they are scattered back.
        MLA latent values (N, L) go into the leading L lanes of an (L, P,
        page, W >= L) pool, which has no head axis, as a row scatter. The
        rest of a latent row is written as zeros: a whole-row write is one
        scatter, where a partial one compiles to a loop over the rows."""
        if layer is None:
            return self.scatter(pool[None], slots, positions, values, 0)[0]
        pages = self.block_tables[slots, positions // self.page_size]
        offsets = positions % self.page_size
        values = values.astype(pool.dtype)
        if values.ndim == 3:
            # writer[n, o]: the last row m writing page pages[n] at offset o
            rows = jnp.arange(pages.shape[0])
            hit = ((pages[:, None, None] == pages[None, None, :])
                   & (offsets[None, None, :] == jnp.arange(self.page_size)[None, :, None]))
            writer = jnp.max(jnp.where(hit, rows, -1), axis=-1)  # (N, page)
            new = values[jnp.maximum(writer, 0)].transpose(0, 2, 1, 3)  # (N, KV, page, d)
            merged = jnp.where((writer >= 0)[:, None, :, None], new, pool[layer, pages])
            return pool.at[layer, pages].set(merged)
        values = jnp.pad(values, ((0, 0), (0, pool.shape[-1] - values.shape[-1])))
        return pool.at[layer, pages, offsets].set(values)


# ---------------------------------------------------------------------------
# packed attention over gathered cache rows
# ---------------------------------------------------------------------------


def _packed_gqa(p, cfg: ModelConfig, spec: LayerSpec, x, slots, positions, cache, inv_freq,
                paged: Optional["PagedView"] = None, layer=None):
    N, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv"):
        q = dense(p["wq"], x).reshape(N, 1, cfg.n_heads, hd)
        k = dense(p["wk"], x).reshape(N, 1, cfg.n_kv_heads, hd)
        v = dense(p["wv"], x).reshape(N, 1, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(p["q_norm"], q, cfg.norm_eps)
            k = rms_norm(p["k_norm"], k, cfg.norm_eps)
        pos2 = positions[:, None]  # (N,1)
        q = apply_rope(q, pos2, inv_freq)[:, 0]  # (N,H,hd)
        k = apply_rope(k, pos2, inv_freq)[:, 0]
        v = v[:, 0]

    KV = cfg.n_kv_heads
    G = cfg.n_heads // KV
    window = cfg.local_window if spec.attn_kind == "local" else None
    if paged is not None:
        # physical page pool: writes scatter through the block table, reads
        # run ONE mixed-batch ragged call over the step's segment layout —
        # decode rows and prefill chunks together, each chunk a causal
        # q-block whose KV pages are read once per chunk: O(sum_seg len)
        # instead of O(N * S_max) or one prefix read per chunk token
        from repro.kernels.paged_attention import ragged_mixed_attention

        with jax.named_scope("kv_pool"):
            ck = paged.scatter(cache["k"], slots, positions, k, layer)
            cv = paged.scatter(cache["v"], slots, positions, v, layer)
        with jax.named_scope("attention"):
            o = ragged_mixed_attention(
                q.reshape(N, KV, G, hd).astype(x.dtype),
                ck, cv,
                paged.cu_q_lens, paged.kv_lens, paged.seg_tables(), layer,
                qb=paged.q_block,
                window=window, softcap=cfg.attn_logit_softcap,
                use_kernel=paged.use_kernel, interpret=paged.interpret,
            ).reshape(N, cfg.n_heads * hd)
        with jax.named_scope("o_proj"):
            return dense(p["wo"], o), {"k": ck, "v": cv}

    with jax.named_scope("kv_pool"):
        new_cache = _dense_write(cache, layer, slots, positions, {"k": k, "v": v})
        ck, cv = (_at(new_cache[n], layer) for n in ("k", "v"))

    with jax.named_scope("attention"):
        S = ck.shape[1]
        kc = ck[slots].astype(x.dtype)  # (N,S,KV,hd)
        vc = cv[slots].astype(x.dtype)
        qg = q.reshape(N, KV, G, hd)
        s = jnp.einsum("nkgh,nskh->nkgs", qg, kc).astype(jnp.float32) / hd**0.5
        s = softcap(s, cfg.attn_logit_softcap)
        k_pos = jnp.arange(S)[None, :]
        ok = k_pos <= positions[:, None]
        if window is not None:
            ok &= k_pos > positions[:, None] - window
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("nkgs,nskh->nkgh", probs, vc).reshape(N, cfg.n_heads * hd)
    with jax.named_scope("o_proj"):
        return dense(p["wo"], o), new_cache


def _packed_mla(p, cfg: ModelConfig, x, slots, positions, cache, inv_freq,
                paged: Optional["PagedView"] = None, layer=None):
    from repro.models.attention import _mla_qkv_rope  # same math, (N,1) shaped

    N, _ = x.shape
    H = cfg.n_heads
    nope, rope, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = mla_scale(cfg)
    with jax.named_scope("qkv"):
        q_nope, q_rope, ckv, krope = _mla_qkv_rope(
            p, cfg, x[:, None, :], positions[:, None], inv_freq)
        q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]  # (N,H,*)
        ckv, krope = ckv[:, 0], krope[:, 0]  # (N,L), (N,rope)
        # absorbed form: queries into the latent space through W_uk, and
        # (below, under o_proj) latent outputs out through W_uv
        w_up = p["kv_up"]["w"].reshape(cfg.kv_lora_rank, H, nope + vh)
        w_uk, w_uv = w_up[..., :nope], w_up[..., nope:]
        q_eff = jnp.einsum("nhp,lhp->nhl", q_nope, w_uk.astype(x.dtype))

    with jax.named_scope("kv_pool"):
        if paged is not None:
            cc = paged.scatter(cache["ckv"], slots, positions, ckv, layer)
            cr = paged.scatter(cache["krope"], slots, positions, krope, layer)
            new_cache = {"ckv": cc, "krope": cr}
        else:
            new_cache = _dense_write(cache, layer, slots, positions,
                                     {"ckv": ckv, "krope": krope})

    with jax.named_scope("attention"):
        if paged is not None:
            # one mixed-batch call over the step's segments, absorbed form:
            # each segment's latent and rope pages are read once per chunk
            from repro.kernels.paged_attention import ragged_mixed_mla_attention

            o_lat = ragged_mixed_mla_attention(
                q_eff, q_rope.astype(x.dtype), cc, cr,
                paged.cu_q_lens, paged.kv_lens, paged.seg_tables(), layer,
                qb=paged.q_block, scale=scale,
                use_kernel=paged.use_kernel, interpret=paged.interpret,
            ).astype(x.dtype)
        else:
            cc, cr = (_at(new_cache[n], layer) for n in ("ckv", "krope"))
            c = cc[slots].astype(x.dtype)  # (N,S,L)
            kr = cr[slots].astype(x.dtype)  # (N,S,rope)
            k_pos = jnp.arange(cc.shape[1])[None, :]
            s = jnp.einsum("nhl,nsl->nhs", q_eff, c) + jnp.einsum("nhr,nsr->nhs", q_rope, kr)
            s = s.astype(jnp.float32) * scale
            ok = k_pos <= positions[:, None]
            s = jnp.where(ok[:, None, :], s, NEG_INF)
            probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            o_lat = jnp.einsum("nhs,nsl->nhl", probs, c)
    with jax.named_scope("o_proj"):
        o = jnp.einsum("nhl,lhv->nhv", o_lat, w_uv.astype(x.dtype)).reshape(N, H * vh)
        return dense(p["wo"], o), new_cache


def _dense_write(cache, layer, slots, positions, values):
    """The dense slot cache with each row's values written at (slot,
    position), in layer ``layer`` of caches stacked over layers (unstacked
    caches without it)."""
    at = (slots, positions) if layer is None else (layer, slots, positions)
    return {n: cache[n].at[at].set(v.astype(cache[n].dtype)) for n, v in values.items()}


def _at(c, layer):
    return c if layer is None else c[layer]


def _packed_layer(p, cfg, spec, x, slots, positions, cache, inv_freq, paged=None,
                  valid=None, layer=None):
    """One layer over the packed rows: (x, new cache, routed rows per
    expert (n_experts,) for a MoE layer, else None). ``valid`` marks the
    real rows the experts route. With ``layer`` (a scanned layer) the
    cache and the experts' weights are the whole stacks over layers
    (``_split_experts``), of which that layer is read and written."""
    with jax.named_scope("qkv"):
        hn = rms_norm(p["norm1"], x, cfg.norm_eps)
    if cfg.mla:
        y, new_cache = _packed_mla(p["mixer"], cfg, hn, slots, positions, cache, inv_freq,
                                   paged=paged, layer=layer)
    else:
        y, new_cache = _packed_gqa(p["mixer"], cfg, spec, hn, slots, positions, cache, inv_freq,
                                   paged=paged, layer=layer)
    with jax.named_scope("o_proj"):
        if cfg.post_norm:
            y = rms_norm(p["post_norm1"], y, cfg.norm_eps)
        x = x + y
    rows = None
    if spec.ffn != "none":
        with jax.named_scope("mlp"):
            hn = rms_norm(p["norm2"], x, cfg.norm_eps)
        if spec.ffn == "dense":
            with jax.named_scope("mlp"):
                y = ffn(p["ffn"], hn, cfg.act, cfg.glu)
        else:  # routed experts under their own scopes, not under mlp
            y, rows = moe.moe_routed(
                p["ffn"], cfg, hn, valid, layer=layer,
                use_kernel=paged is not None and paged.use_kernel,
                interpret=paged is not None and paged.interpret)
            if cfg.n_shared_experts:
                with jax.named_scope("mlp"):
                    y = y + ffn(p["ffn"]["shared"], hn, cfg.act, cfg.glu)
        with jax.named_scope("mlp"):
            if cfg.post_norm:
                y = rms_norm(p["post_norm2"], y, cfg.norm_eps)
            x = x + y
    return x, new_cache, rows


def _split_experts(periods):
    """(the scanned layers' parameters without their routed experts, {period
    position: experts stacked over periods}). The experts stay out of the
    scan's per-layer slices: the grouped matmul reads its layer from the
    whole stack, so no layer's experts are copied out each step."""
    rest, experts = {}, {}
    for k, p in periods.items():
        if "experts" in p.get("ffn", {}):
            experts[k] = p["ffn"]["experts"]
            p = {**p, "ffn": {n: v for n, v in p["ffn"].items() if n != "experts"}}
        rest[k] = p
    return rest, experts


def packed_step(model: Model, params, cache, tokens, slots, positions,
                paged: Optional[PagedView] = None):
    """tokens/slots/positions: (N,) -> (logits (N, vocab), new cache,
    expert rows). ``expert rows`` is None for a model without MoE layers,
    else the (MoE layers, n_experts) int32 count of real rows routed to each
    expert this step (prefix layers first, then the scanned ones).

    Padding rows point at a scratch slot whose table names only the scratch
    page (paged mode) or at an extra dense cache row (dense mode); their
    outputs are ignored by the caller.

    With ``paged`` set, the cache is a physical page pool and attention runs
    the ragged block-table path (writes and reads both route through the
    mirror's real page ids, each row attending up to its own position);
    otherwise the dense ``cache[slots]`` gather over slot rows.
    """
    cfg = model.cfg
    assert supports_packed(cfg), cfg.name
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(model.dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model**0.5, model.dtype)

    # the real rows (paged: rows past the last segment are padding)
    valid = None if paged is None else jnp.arange(x.shape[0]) < paged.cu_q_lens[-1]
    new_prefix, rows = [], []
    for i in range(cfg.n_prefix_layers):
        x, nc, r = _packed_layer(
            params["stack"]["prefix"][i], cfg, cfg.layer_specs[i], x, slots, positions,
            cache["prefix"][i], model.inv_freq, paged=paged, valid=valid,
        )
        new_prefix.append(nc)
        rows += [] if r is None else [r[None]]

    # the layer-stacked cache rides the scan CARRY (not xs -> ys) whole:
    # each period writes the step's K/V into its layer in place and the
    # kernels read that layer by index, so no layer's pool is sliced out
    # or written back, and a donated cache is updated without a copy
    scanned, experts = _split_experts(params["stack"]["periods"])

    def body(carry, xs):
        x, periods = carry
        p_period, layer = xs
        new_periods, period_rows = {}, []
        for i in range(cfg.scan_period):
            p = p_period[str(i)]
            if str(i) in experts:
                p = {**p, "ffn": {**p["ffn"], "experts": experts[str(i)]}}
            x, new_periods[str(i)], r = _packed_layer(
                p, cfg, cfg.period_specs[i], x, slots, positions,
                periods[str(i)], model.inv_freq, paged=paged, valid=valid, layer=layer,
            )
            period_rows += [] if r is None else [r]
        return (x, new_periods), jnp.stack(period_rows) if period_rows else None

    if cfg.n_periods:
        with jax.named_scope("kv_pool"):  # the index only selects pool layers
            layers = jnp.arange(cfg.n_periods)
        (x, new_periods), scan_rows = jax.lax.scan(
            body, (x, cache["periods"]), (scanned, layers),
        )
        if scan_rows is not None:  # (periods, MoE layers per period, E)
            rows.append(scan_rows.reshape(-1, scan_rows.shape[-1]))
    else:
        new_periods = cache["periods"]

    with jax.named_scope("lm_head"):
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = softcap((x @ w.astype(x.dtype)).astype(jnp.float32),
                         cfg.final_logit_softcap)
    return (logits, {"prefix": new_prefix, "periods": new_periods},
            jnp.concatenate(rows) if rows else None)
