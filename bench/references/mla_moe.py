"""Plain reference for DeepSeek-V2-style decoders: latent attention (MLA)
with a direct query projection, YaRN rope, a leading dense SwiGLU layer and
sparse-expert layers with shared experts (DeepSeek-V2-Lite).

Written from the published description (Hugging Face
``modeling_deepseek.py``: ``DeepseekV2Attention``,
``DeepseekV2YarnRotaryEmbedding``, ``MoEGate``, ``DeepseekV2MoE``), not from
the program:

    h = embed[tokens]
    per layer:  x = rmsnorm(h) * g1
                q = x Wq                          (q_lora_rank null: no norm)
                q_nope, q_pe = q[..., :nope], q[..., nope:]   per head
                c, k_pe = (x Wkv_a)[:, :L], (x Wkv_a)[:, L:]
                k_nope, v = (rmsnorm(c) * gkv) Wkv_b          per head
                q_pe, k_pe = rope(q_pe), rope(k_pe)   YaRN frequencies
                o = softmax([q_nope|q_pe] [k_nope|k_pe]^T * scale, causal) v
                    scale = mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)
                h = h + o Wo
                x = rmsnorm(h) * g2
                dense layer: h = h + (silu(x Wg) * (x Wu)) Wd
                MoE layer:   p = softmax(x Wr) (float32), top-k greedy,
                             weights not renormalised, routed_scaling_factor 1
                             h = h + sum_k p_k E_k(x) + S(x)   (S: the shared
                             experts as one SwiGLU of their summed width)
    logits = (rmsnorm(h) * gf) Wout

K and V are expanded from the latent (the non-absorbed form), which the
program never computes: it attends in the latent space. The routed experts
run per expert over the rows routed to it (routing read on the host), so the
reference computes what a deployment computes and no more.

Departure, noted: HF de-interleaves the rope dimensions of q_pe and k_pe
before ``rotate_half``; this reference and the program rotate the halves as
they lie. That equals a fixed permutation of the rope columns of Wq and
Wkv_a, which weights drawn independently per element cannot tell apart.

Weights are drawn here, as ``dense_gqa`` draws them: ``draw_params`` builds
the served tree on the device (bf16 matrices, fp32 norm scales) in one
jitted call, the expert layers one at a time (``lax.map``) so the draw's
temporaries stay one layer's size; the forward draws each layer again with
the same keys and keeps one layer's weights at a time. ``fp8=True`` is the
control: every linear layer's input and weight (router and experts
included), and the K/V attended, rounded to float8 e4m3.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import segment_keys
from bench.references.dense_gqa import (
    F_BLOCK,
    HIGHEST,
    Q_BLOCK,
    _linear,
    _matrix,
    _q8,
    _rms,
    padded_len,
    root_key,
)

_LEAF = {"wq": 1, "kv_down": 2, "kv_up": 3, "wo": 4, "up": 5, "gate": 6,
         "down": 7, "norm1": 11, "norm2": 12, "kv_norm": 13, "router": 15,
         "s_up": 16, "s_gate": 17, "s_down": 18}


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "L": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "vh": cfg["v_head_dim"],
            "ff": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "V": cfg["vocab_size"]}


def is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0


# ------------------------------------------------------------------ weights


def draw_layer(cfg: dict, key, moe: bool) -> dict:
    """One decoder layer's weights in the served layout and types. Scales
    as in ``dense_gqa.draw_layer``: queries by ``q_scale`` (attention picks
    out a few keys), the output projections (Wo, every expert's and the
    shared experts' Wd) by ``out_scale``; the router by ``router_gain``."""
    n = dims(cfg)
    d, H, L, rope = n["d"], n["H"], n["L"], n["rope"]
    w = cfg["weights"]
    k = lambda name: jax.random.fold_in(key, _LEAF[name])
    mat = lambda name, shape, gain=1.0: {
        "w": _matrix(k(name), shape, gain / math.sqrt(shape[-2]))}
    norm = lambda name, m: {"scale": 1.0 + w["norm_jitter"] * jax.random.uniform(
        k(name), (m,), jnp.float32, -1.0, 1.0)}
    mixer = {"wq": mat("wq", (d, H * (n["nope"] + rope)), w["q_scale"]),
             "kv_down": mat("kv_down", (d, L + rope)), "kv_norm": norm("kv_norm", L),
             "kv_up": mat("kv_up", (L, H * (n["nope"] + n["vh"]))),
             "wo": mat("wo", (H * n["vh"], d), w["out_scale"])}
    swiglu = lambda pre, shape_in, shape_out: {
        "up": mat(pre + "up", shape_in), "gate": mat(pre + "gate", shape_in),
        "down": mat(pre + "down", shape_out, w["out_scale"])}
    if moe:
        E, fe, fs = n["E"], n["fe"], n["fs"]
        ffn = {"router": mat("router", (d, E), w["router_gain"]),
               "experts": swiglu("", (E, d, fe), (E, fe, d)),
               "shared": swiglu("s_", (d, fs), (fs, d))}
    else:
        ffn = swiglu("", (d, n["ff"]), (n["ff"], d))
    return {"norm1": norm("norm1", d), "mixer": mixer, "norm2": norm("norm2", d),
            "ffn": ffn}


def _keys(root):
    return {n: jax.random.fold_in(root, i) for i, n in
            enumerate(("embed", "stack", "head", "final_norm"))}


def _final_norm(cfg, keys):
    return 1.0 + cfg["weights"]["norm_jitter"] * jax.random.uniform(
        keys["final_norm"], (cfg["hidden_size"],), jnp.float32, -1.0, 1.0)


@functools.lru_cache(maxsize=None)
def _draws(cfg_json: str):
    """Jitted draws for one configuration (cached so each compiles once)."""
    cfg = json.loads(cfg_json)
    n, nl = dims(cfg), cfg["num_hidden_layers"]
    first = cfg["first_k_dense_replace"]
    layer_key = lambda root, i: jax.random.fold_in(_keys(root)["stack"], i)
    embed = lambda root: _matrix(_keys(root)["embed"], (n["V"], n["d"]),
                                 cfg["weights"]["embed_std"])
    head = lambda root: _matrix(_keys(root)["head"], (n["d"], n["V"]),
                                cfg["weights"]["head_std"])

    def params(root):
        keys = _keys(root)
        out = {"embed": embed(root),
               "stack": {"prefix": [draw_layer(cfg, layer_key(root, i), False)
                                    for i in range(first)],
                         "periods": {"0": jax.lax.map(
                             lambda i: draw_layer(cfg, layer_key(root, i), True),
                             jnp.arange(first, nl))}},
               "final_norm": {"scale": _final_norm(cfg, keys)}}
        if not cfg["tie_word_embeddings"]:
            out["lm_head"] = head(root)
        return out

    return {
        "params": jax.jit(params),
        "layer": jax.jit(lambda root, i, moe: draw_layer(cfg, layer_key(root, i), moe),
                         static_argnums=(2,)),
        "embed": jax.jit(embed),
        "head": jax.jit(head),
        "final_norm": jax.jit(lambda root: _final_norm(cfg, _keys(root))),
    }


def _cfg_json(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def draw_params(cfg: dict, seed: int) -> dict:
    """The served parameter tree: ``{"embed", "stack": {"prefix": [dense
    layers], "periods": {"0": MoE layers stacked}}, "final_norm",
    ["lm_head"]}``, drawn on the device in one jitted call."""
    if cfg["moe_layer_freq"] != 1:
        raise ValueError("only every layer after the dense ones is MoE here")
    return _draws(_cfg_json(cfg))["params"](root_key(seed))


# ------------------------------------------------------------------ forward


def yarn(cfg: dict):
    """(inverse frequencies (rope/2,), softmax scale) of HF's YaRN rope, in
    float32 as HF computes them."""
    rope, nope = cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"]
    base = float(cfg["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, rope, 2, dtype=np.float32) / rope)
    rs = cfg.get("rope_scaling")
    scale = (nope + rope) ** -0.5
    if not rs:
        return extra.astype(np.float32), scale
    f = float(rs["factor"])
    mscale = lambda m: 1.0 if f <= 1 else 0.1 * m * math.log(f) + 1.0
    corr = lambda turns: (rope * math.log(rs["original_max_position_embeddings"]
                                          / (turns * 2 * math.pi)) / (2 * math.log(base)))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), rope - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rope // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # HF's inv_freq_mask
    inv = (extra / f) * (1 - keep) + extra * keep
    if mscale(rs["mscale"]) != mscale(rs["mscale_all_dim"]):
        raise ValueError("cos/sin scaling other than 1 is not written here")
    if rs.get("mscale_all_dim"):
        scale *= mscale(rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), scale


def _rope(x, pos, inv):
    """Rotate-half rope (x: (T, heads, rope)) at the given frequencies."""
    ang = pos[:, None].astype(jnp.float32) * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _attention(h, g1, mixer, inv, cfg_items, fp8):
    """h + MLA(rmsnorm(h)), K and V expanded per head from the latent."""
    c = dict(cfg_items)
    H, L, nope, rope, vh, eps = c["H"], c["L"], c["nope"], c["rope"], c["vh"], c["eps"]
    T = h.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    m = jax.tree.map(lambda a: a.astype(jnp.float32), mixer)
    x = _rms(h, g1, eps)
    q = _linear(x, m["wq"]["w"], None, fp8).reshape(T, H, nope + rope)
    ckv = _linear(x, m["kv_down"]["w"], None, fp8)
    kv = _linear(_rms(ckv[:, :L], m["kv_norm"]["scale"], eps), m["kv_up"]["w"], None,
                 fp8).reshape(T, H, nope + vh)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)], -1)
    k_pe = _rope(ckv[:, None, L:], pos, inv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (T, H, rope))], -1)
    v = kv[..., nope:]
    if fp8:
        k, v = _q8(k, -1), _q8(v, -1)

    def attend(args):
        qi, i = args
        s = jnp.einsum("qhe,the->hqt", qi, k, precision=HIGHEST) * c["scale"]
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, H, nope + rope)
    o = jax.lax.map(attend, (qb, jnp.arange(T // Q_BLOCK))).reshape(T, H * vh)
    return h + _linear(o, m["wo"]["w"], None, fp8)


def _swiglu(x, f, fp8):
    f = jax.tree.map(lambda a: a.astype(jnp.float32), f)
    a = jax.nn.silu(_linear(x, f["gate"]["w"], None, fp8)) * _linear(x, f["up"]["w"], None, fp8)
    return _linear(a, f["down"]["w"], None, fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _norm_and_swiglu(h, g2, f, eps, fp8):
    """(rmsnorm(h), the SwiGLU ``f`` of it), in row blocks."""
    x = _rms(h, g2, eps)
    y = jax.lax.map(lambda xb: _swiglu(xb, f, fp8),
                    x.reshape(-1, F_BLOCK, x.shape[-1])).reshape(x.shape)
    return x, y


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def _route(x, w_router, k, fp8):
    """Top-k of the router's float32 softmax, greedy, not renormalised."""
    p = jax.nn.softmax(_linear(x, w_router.astype(jnp.float32), None, fp8), axis=-1)
    return jax.lax.top_k(p, k)


@functools.partial(jax.jit, static_argnames=("fp8",), donate_argnums=(0,))
def _expert(y, x, rows, wt, experts, e, fp8):
    """y[rows] += wt * E_e(x[rows]) for one expert's rows (padded with
    weight 0)."""
    f = jax.tree.map(lambda a: a[e], experts)
    return y.at[rows].add(_swiglu(x[rows], f, fp8) * wt[:, None])


def _moe(x, y, f, n_real, k, fp8):
    """y (the shared experts' output) plus every row's top-k experts, each
    expert run over the rows routed to it."""
    top_w, top_i = (np.asarray(a) for a in _route(x, f["router"]["w"], k, fp8))
    top_i = top_i[:n_real]
    for e in range(f["experts"]["up"]["w"].shape[0]):
        r, j = np.nonzero(top_i == e)
        if not len(r):
            continue
        size = max(8, 1 << (len(r) - 1).bit_length())  # pow2 buckets: few compiles
        rows = np.zeros((size,), np.int32)
        wt = np.zeros((size,), np.float32)
        rows[:len(r)], wt[:len(r)] = r, top_w[r, j]
        y = _expert(y, x, jnp.asarray(rows), jnp.asarray(wt), f["experts"],
                    jnp.int32(e), fp8)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _logits(h_rows, g, w_out, eps, fp8):
    return _linear(_rms(h_rows, g, eps), w_out.astype(jnp.float32), None, fp8)


def logits_at(cfg: dict, seed: int, tokens, rows, fp8: bool = False) -> np.ndarray:
    """Float32 logits (len(rows), vocab) of the sequence ``tokens`` (positions
    0..len-1) at the positions ``rows``; the control rounds to fp8."""
    n = dims(cfg)
    draws = _draws(_cfg_json(cfg))
    root = root_key(seed)
    T = len(tokens)
    tok = np.zeros((padded_len(T),), np.int32)
    tok[:T] = tokens
    embed = draws["embed"](root)
    h = embed[jnp.asarray(tok)].astype(jnp.float32)
    inv, scale = yarn(cfg)
    eps = cfg["rms_norm_eps"]
    items = tuple(sorted(dict(H=n["H"], L=n["L"], nope=n["nope"], rope=n["rope"],
                              vh=n["vh"], eps=eps, scale=scale).items()))
    for i in range(cfg["num_hidden_layers"]):
        moe = is_moe(cfg, i)
        lw = draws["layer"](root, i, moe)
        h = _attention(h, lw["norm1"]["scale"], lw["mixer"], jnp.asarray(inv), items, fp8)
        f = lw["ffn"]
        x, y = _norm_and_swiglu(h, lw["norm2"]["scale"], f["shared"] if moe else f,
                                eps, fp8)
        if moe:
            y = _moe(x, y, f, T, n["K"], fp8)
        h = h + y
        del lw, f, x, y
    w_out = embed.T if cfg["tie_word_embeddings"] else draws["head"](root)
    m = len(rows)
    idx = np.zeros((max(8, 1 << (m - 1).bit_length()),), np.int32)
    idx[:m] = rows
    out = _logits(h[jnp.asarray(idx)], draws["final_norm"](root), w_out, eps, fp8)
    return np.asarray(out)[:m]


# ------------------------------------------------------------------ counts


def layer_matmul_params(cfg: dict) -> float:
    """Weights one token multiplies in one layer, the mean over the layers:
    the MLA projections (the latent up-projection once: absorbed into the
    queries and outputs, or expanding K and V, it is the same weights), and
    the dense SwiGLU or the router, the top-k routed experts and the shared
    experts."""
    n = dims(cfg)
    d, H = n["d"], n["H"]
    attn = (d * H * (n["nope"] + n["rope"]) + d * (n["L"] + n["rope"])
            + n["L"] * H * (n["nope"] + n["vh"]) + H * n["vh"] * d)
    moe = d * n["E"] + 3 * d * (n["K"] * n["fe"] + n["fs"])
    L = cfg["num_hidden_layers"]
    per = [attn + (moe if is_moe(cfg, i) else 3 * d * n["ff"]) for i in range(L)]
    return sum(per) / L


def attention_flops(cfg: dict, q_lens, kv_lens) -> float:
    """One layer's latent attention FLOPs, absorbed form: per query head and
    key, scores over the latent and rope widths (L + rope) and the latent
    values (L), 2 FLOPs a multiply-add."""
    n = dims(cfg)
    keys = sum(segment_keys(q, k) for q, k in zip(q_lens, kv_lens) if q > 0)
    return 2.0 * n["H"] * (n["L"] + n["rope"] + n["L"]) * keys


def attention_bytes(cfg: dict, q_lens, kv_lens, page: int) -> float:
    """One layer's latent attention bytes (bf16): each segment's latent and
    rope pages (whole pages), every row's absorbed queries (H x (L + rope))
    read and latent outputs (H x L) written."""
    n = dims(cfg)
    pages = sum(-(-k // page) for q, k in zip(q_lens, kv_lens) if q > 0)
    rows = sum(q_lens)
    return 2.0 * (pages * page * (n["L"] + n["rope"])
                  + rows * n["H"] * (2 * n["L"] + n["rope"]))

