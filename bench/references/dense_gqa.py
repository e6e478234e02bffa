"""Plain reference for dense grouped-query-attention decoders (Qwen2, Qwen3).

Written from the published model descriptions (Hugging Face
``modeling_qwen2.py`` / ``modeling_qwen3.py``), not from the program:

    h = embed[tokens]
    per layer:  x = rmsnorm(h) * g1
                q, k, v = x Wq (+bq), x Wk (+bk), x Wv (+bv)
                q, k = rmsnorm_head(q) * gq, rmsnorm_head(k) * gk   (Qwen3 only)
                q, k = rope(q), rope(k)          rotate-half, theta from config
                o = softmax(q k^T / sqrt(head_dim), causal) v    (GQA: KV heads
                    shared by num_attention_heads / num_key_value_heads queries)
                h = h + o Wo
                x = rmsnorm(h) * g2
                h = h + (silu(x Wg) * (x Wu)) Wd
    logits = (rmsnorm(h) * gf) Wout,  Wout = embed^T when tied

The module also draws the weights. ``draw_params`` builds the served model's
whole parameter tree on the device in one jitted call, in the types it is
served in (bf16 matrices, fp32 norm scales and biases); the reference draws
the same values again layer by layer with ``draw_layer``, so it takes
nothing the program made. The draws depend on the seed and the
configuration file only.

``logits_at`` runs the forward in float32 at ``Precision.HIGHEST`` layer by
layer, attention in query blocks, so a 20K-token context of a 5120-wide
model fits next to one layer's weights. With ``fp8=True`` it is the control:
every linear layer's input and weight, and the K/V it attends, are rounded to
float8 e4m3 with per-row / per-column scales, the precision below the bf16
the configurations serve in.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 1024  # sequences are padded to a bucket of this (bounds compiles)
Q_BLOCK = 256  # attention query rows per block
F_BLOCK = BLOCK  # FFN rows per block
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

# fixed per-leaf stream ids under each layer's key
_LEAF = {"wq": 1, "wk": 2, "wv": 3, "wo": 4, "up": 5, "gate": 6, "down": 7,
         "bq": 8, "bk": 9, "bv": 10, "norm1": 11, "norm2": 12, "q_norm": 13,
         "k_norm": 14}


def dims(cfg: dict):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return (d, H, cfg["num_key_value_heads"], cfg.get("head_dim", d // H),
            cfg["intermediate_size"], cfg["vocab_size"])


def root_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


def _matrix(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.bfloat16) \
        * jnp.bfloat16(std)


def draw_layer(cfg: dict, key) -> dict:
    """One decoder layer's weights in the served layout and types.

    Random weights are scaled so the model depends on its context, as a
    trained one does: queries are scaled by ``q_scale`` (through Wq, or
    through the q-norm scale where q is normalized), so attention picks out
    a few keys instead of averaging thousands, and the output projections
    (Wo, Wd) by ``out_scale``, so the residual stream keeps what earlier
    layers and the token put there. A KV page written to the wrong place or
    not at all then changes the tokens served."""
    d, H, KV, hd, ff, _ = dims(cfg)
    w = cfg["weights"]
    k = lambda name: jax.random.fold_in(key, _LEAF[name])
    mat = lambda name, din, dout, gain=1.0: {
        "w": _matrix(k(name), (din, dout), gain / math.sqrt(din))}
    norm = lambda name, n, gain=1.0: {"scale": gain * (
        1.0 + w["norm_jitter"] * jax.random.uniform(k(name), (n,), jnp.float32,
                                                    -1.0, 1.0))}
    qk = cfg["qk_norm"]
    mixer = {"wq": mat("wq", d, H * hd, 1.0 if qk else w["q_scale"]),
             "wk": mat("wk", d, KV * hd), "wv": mat("wv", d, KV * hd),
             "wo": mat("wo", H * hd, d, w["out_scale"])}
    if cfg["attention_bias"]:
        for name, n in (("wq", H * hd), ("wk", KV * hd), ("wv", KV * hd)):
            mixer[name]["b"] = w["bias_std"] * jax.random.normal(
                k("b" + name[1]), (n,), jnp.float32)
    if qk:
        mixer["q_norm"] = norm("q_norm", hd, w["q_scale"])
        mixer["k_norm"] = norm("k_norm", hd)
    return {"norm1": norm("norm1", d), "mixer": mixer, "norm2": norm("norm2", d),
            "ffn": {"up": mat("up", d, ff), "gate": mat("gate", d, ff),
                    "down": mat("down", ff, d, w["out_scale"])}}


def _keys(root):
    return {n: jax.random.fold_in(root, i) for i, n in
            enumerate(("embed", "stack", "head", "final_norm"))}


def _embed(cfg, keys):
    d, _, _, _, _, V = dims(cfg)
    return _matrix(keys["embed"], (V, d), cfg["weights"]["embed_std"])


def _head(cfg, keys):
    d, _, _, _, _, V = dims(cfg)
    return _matrix(keys["head"], (d, V), cfg["weights"]["head_std"])


def _final_norm(cfg, keys):
    d = dims(cfg)[0]
    return 1.0 + cfg["weights"]["norm_jitter"] * jax.random.uniform(
        keys["final_norm"], (d,), jnp.float32, -1.0, 1.0)


@functools.lru_cache(maxsize=None)
def _draws(cfg_json: str):
    """Jitted draws for one configuration (cached so each compiles once)."""
    cfg = json.loads(cfg_json)
    L = cfg["num_hidden_layers"]

    def params(root):
        keys = _keys(root)
        layer_keys = jax.vmap(lambda i: jax.random.fold_in(keys["stack"], i))(
            jnp.arange(L))
        out = {"embed": _embed(cfg, keys),
               "stack": {"prefix": [],
                         "periods": {"0": jax.vmap(
                             lambda k: draw_layer(cfg, k))(layer_keys)}},
               "final_norm": {"scale": _final_norm(cfg, keys)}}
        if not cfg["tie_word_embeddings"]:
            out["lm_head"] = _head(cfg, keys)
        return out

    return {
        "params": jax.jit(params),
        "layer": jax.jit(lambda root, i: draw_layer(
            cfg, jax.random.fold_in(_keys(root)["stack"], i))),
        "embed": jax.jit(lambda root: _embed(cfg, _keys(root))),
        "head": jax.jit(lambda root: _head(cfg, _keys(root))),
        "final_norm": jax.jit(lambda root: _final_norm(cfg, _keys(root))),
    }


def _cfg_json(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def draw_params(cfg: dict, seed: int) -> dict:
    """The served parameter tree, drawn on the device in one jitted call:
    ``{"embed", "stack": {"prefix": [], "periods": {"0": layers}},
    "final_norm", ["lm_head"]}`` with every layer leaf stacked over
    ``num_hidden_layers``."""
    return _draws(_cfg_json(cfg))["params"](root_key(seed))


# ------------------------------------------------------------------ forward


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _linear(x, w, b=None, fp8=False):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    y = jnp.dot(x, w, precision=HIGHEST)
    return y if b is None else y + b


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head (x: (T, heads, hd))."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _layer(h, lw, cfg_items, fp8):
    cfg = dict(cfg_items)
    d, H, KV, hd, ff, _ = cfg["dims"]
    eps, theta, G = cfg["eps"], cfg["theta"], H // KV
    T = h.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    lw = f32(lw)
    m = lw["mixer"]
    x = _rms(h, lw["norm1"]["scale"], eps)
    q = _linear(x, m["wq"]["w"], m["wq"].get("b"), fp8).reshape(T, H, hd)
    k = _linear(x, m["wk"]["w"], m["wk"].get("b"), fp8).reshape(T, KV, hd)
    v = _linear(x, m["wv"]["w"], m["wv"].get("b"), fp8).reshape(T, KV, hd)
    if "q_norm" in m:
        q = _rms(q, m["q_norm"]["scale"], eps)
        k = _rms(k, m["k_norm"]["scale"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if fp8:
        k, v = _q8(k, -1), _q8(v, -1)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, KV, G, hd)

    def attend(args):
        qi, i = args
        s = jnp.einsum("qkgh,tkh->kgqt", qi, k, precision=HIGHEST) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkh->qkgh", p, v, precision=HIGHEST)

    o = jax.lax.map(attend, (qb, jnp.arange(T // Q_BLOCK))).reshape(T, H * hd)
    h = h + _linear(o, m["wo"]["w"], None, fp8)

    def mlp(hb):
        x = _rms(hb, lw["norm2"]["scale"], eps)
        f = lw["ffn"]
        a = jax.nn.silu(_linear(x, f["gate"]["w"], None, fp8)) \
            * _linear(x, f["up"]["w"], None, fp8)
        return hb + _linear(a, f["down"]["w"], None, fp8)

    return jax.lax.map(mlp, h.reshape(T // F_BLOCK, F_BLOCK, d)).reshape(T, d)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _logits(h_rows, g, w_out, eps, fp8):
    x = _rms(h_rows, g, eps)
    return _linear(x, w_out.astype(jnp.float32), None, fp8)


def padded_len(n: int) -> int:
    """The sequence bucket: BLOCK times 1, 2, 3, 4, 6, 8, 12, 16, ... (a power
    of two or 1.5 times one), so a run's sequences share a few compiles."""
    sizes = sorted({BLOCK << k for k in range(8)} | {3 * BLOCK << k for k in range(7)})
    return next(b for b in sizes if b >= n)


def logits_at(cfg: dict, seed: int, tokens, rows, fp8: bool = False) -> np.ndarray:
    """Float32 logits (len(rows), vocab) of the sequence ``tokens`` (positions
    0..len-1) at the positions ``rows``; the control rounds to fp8."""
    d, H, KV, hd, ff, V = dims(cfg)
    draws = _draws(_cfg_json(cfg))
    root = root_key(seed)
    T = len(tokens)
    tok = np.zeros((padded_len(T),), np.int32)
    tok[:T] = tokens
    embed = draws["embed"](root)
    h = embed[jnp.asarray(tok)].astype(jnp.float32)
    items = (("dims", (d, H, KV, hd, ff, V)), ("eps", cfg["rms_norm_eps"]),
             ("theta", float(cfg["rope_theta"])))
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, draws["layer"](root, i), items, fp8)
    w_out = embed.T if cfg["tie_word_embeddings"] else draws["head"](root)
    n = len(rows)
    idx = np.zeros((max(8, 1 << (n - 1).bit_length()),), np.int32)  # pow2 rows
    idx[:n] = rows
    out = _logits(h[jnp.asarray(idx)], draws["final_norm"](root), w_out,
                  cfg["rms_norm_eps"], fp8)
    return np.asarray(out)[:n]
