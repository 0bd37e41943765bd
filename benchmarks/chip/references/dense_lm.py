"""Plain reference of a dense decoder-only transformer, compressed as run.

Float32 at the highest matmul precision, one whole sequence at a time,
no cache, no kernels, no batching.  It follows the configuration file
and imports nothing of the program.  What it computes:

* weights: the benchmark's seeded bf16 weights (``weights.py``), then the
  compression the configuration states.  ``sparse``: each linear stack
  keeps the ``ceil(density * blocks)`` (bk, bn) blocks of largest summed
  |w| over all its layers, and zeroes the rest.  Then every linear
  (``sparse`` and ``quant``) is quantised per output column to int4:
  scale = max(amax / 7, 1e-12), codes = clip(round(w / scale), -7, 7).
* a pre-norm block: x = norm(h); q, k, v = x Wq + bq, x Wk + bk, x Wv + bv;
  rotary embedding (rotate-half, theta from the file) on q and k; unless
  the file's KV cache is ``float``, K and V quantised to int4 per
  (position, head) over the head dimension with the same rule (the
  int4x2 KV cache); causal softmax attention over the
  dequantised K, V with scale 1/sqrt(Dh), grouped heads (head h reads KV
  head h // G); h += o Wo; h += mlp(norm(h)), where the MLP is
  silu(x Wg) * (x Wu) then Wd (``silu``) or gelu_tanh(x Wu) then Wd;
* RMSNorm or LayerNorm with eps from the file, then the head.

``variants`` may add the control: the same reference with the input of
every weight matmul one precision step below the configuration's bf16,
``"fp8"`` (float8 e4m3).

Departures from the source models are listed in each configuration
file under ``reduced`` and ``assumed``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
HEAD_ROWS = 256        # logits rows per head block


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _quant_cols(w, bits=4):
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / qmax,
                        1e-12)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _act_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


LOWER = {"fp8": _act_fp8}


def _kv_int4(x):
    """(S, Hkv, Dh): per (position, head) symmetric int4 over Dh."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 7.0,
                        1e-12)
    return jnp.clip(jnp.round(x / scale), -7, 7) * scale


def block_bitmap(stack, block, density):
    """(K//bk, N//bn) keep-map of a (L, K, N) stack by summed |w|."""
    L, K, N = stack.shape
    bk, bn = block
    score = jnp.abs(stack.astype(jnp.float32)).reshape(
        L, K // bk, bk, N // bn, bn).sum(axis=(0, 2, 4))
    n_keep = max(1, math.ceil(density * score.size))
    thresh = jnp.sort(score.ravel())[score.size - n_keep]
    return score >= thresh


@jax.jit
def _effective(w, bitmap):
    """One layer's (K, N) f32 weight as the compressed model holds it."""
    w = w.astype(jnp.float32)
    if bitmap is not None:
        bk = w.shape[0] // bitmap.shape[0]
        bn = w.shape[1] // bitmap.shape[1]
        m = jnp.repeat(jnp.repeat(bitmap, bk, axis=0), bn, axis=1)
        w = w * m
    return _quant_cols(w)


def _norm(kind, eps, p, x):
    if kind == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["g"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _rope(x, theta):
    """x (S, H, Dh), positions 0..S-1, rotate-half."""
    S, _, Dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v):
    """Causal attention; q (S, H, Dh), k/v (S, Hkv, Dh); by query blocks."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    kk = jnp.repeat(k, G, axis=1)          # head h reads kv head h // G
    vv = jnp.repeat(v, G, axis=1)
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, H, Dh) / math.sqrt(Dh)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, kk, precision=HI)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = jnp.arange(S)[None, :] <= rows[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    o = jax.lax.map(one, (jnp.arange(nb), qb))
    return o.reshape(S, H * Dh)


def _layer(h, W, cfg_static, lower):
    kind, eps, theta, H, Hkv, Dh, act, kv_int4 = cfg_static
    lin = (lambda x, w: _mm(LOWER[lower](x), w)) if lower else _mm
    S = h.shape[0]
    x = _norm(kind, eps, W["ln1"], h)
    q = lin(x, W["wq"]) + W.get("bq", 0.0)
    k = lin(x, W["wk"]) + W.get("bk", 0.0)
    v = lin(x, W["wv"]) + W.get("bv", 0.0)
    q = _rope(q.reshape(S, H, Dh), theta)
    k = _rope(k.reshape(S, Hkv, Dh), theta)
    v = v.reshape(S, Hkv, Dh)
    if kv_int4:
        k, v = _kv_int4(k), _kv_int4(v)
    h = h + lin(_attention(q, k, v), W["wo"])
    x = _norm(kind, eps, W["ln2"], h)
    if act == "silu":
        y = jax.nn.silu(lin(x, W["wg"])) * lin(x, W["wu"])
    else:
        y = jax.nn.gelu(lin(x, W["wu"]), approximate=True)
    return h + lin(y, W["wd"])


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def _head_block(h, head, fnorm, kind, eps, lower):
    x = _norm(kind, eps, fnorm, h)
    return _mm(LOWER[lower](x) if lower else x, head)


_head_jit = jax.jit(_head_block, static_argnums=(3, 4, 5))


@jax.jit
def _gaps(ref, ctl, served):
    """Per row: (best - served) in the reference, and (best - the control's
    pick) in the reference."""
    best = ref.max(-1)
    g_srv = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    pick = jnp.argmax(ctl, -1)
    g_ctl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return g_srv, g_ctl


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def gaps(raw: dict, cfg: dict, seqs: Sequence[np.ndarray],
         starts: Sequence[int], served: Sequence[np.ndarray],
         variants=("ref",)) -> Dict[str, List[np.ndarray]]:
    """Gap readings over served tokens.

    ``seqs[i]`` is prompt + served tokens but the last; the served token
    ``served[i][j]`` was produced from position ``starts[i] + j`` (the
    last prompt position is ``starts[i]``).  Returns, per sequence, the
    reference's gap of each served token (``"ref"``), and, where
    ``variants`` holds ``"fp8"``, the gap of the control's own pick at
    each of those positions (``"fp8"``)."""
    D = cfg["hidden_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim", D // H)
    L = cfg["num_hidden_layers"]
    kind = cfg["norm"]
    eps = float(cfg["rms_norm_eps" if kind == "rms" else "norm_epsilon"])
    static = (kind, eps, float(cfg["rope_theta"]), H, Hkv, Dh,
              cfg["hidden_act"], cfg["serve"]["kv_cache"] != "float")
    rules = cfg["compile_rules"]
    block = tuple(rules.get("block", (128, 128)))
    density = rules.get("block_density", 0.25)
    sparse = cfg["policy"] == "sparse"
    blocks = raw["blocks"]

    def bitmap(stack):
        return block_bitmap(stack, block, density) if sparse else None

    lin_names = [n for n in ("wq", "wk", "wv", "wo") if n in blocks["attn"]]
    mlp_names = [n for n in ("wg", "wu", "wd") if n in blocks["mlp"]]
    maps = {n: bitmap(blocks["attn"][n]["w"]) for n in lin_names}
    maps.update({n: bitmap(blocks["mlp"][n]["w"]) for n in mlp_names})

    pads = [-(-len(s) // Q_BLOCK) * Q_BLOCK for s in seqs]
    hs = {v: [] for v in variants}
    for s, P in zip(seqs, pads):
        tok = jnp.asarray(np.pad(np.asarray(s, np.int32), (0, P - len(s))))
        e = _embed(raw["embed"]["w"], tok)
        for v in variants:
            hs[v].append(e)
    for li in range(L):
        W = {}
        for n in lin_names:
            W[n] = _effective(blocks["attn"][n]["w"][li], maps[n])
            if "b" in blocks["attn"][n]:
                W["b" + n[1]] = blocks["attn"][n]["b"][li].astype(
                    jnp.float32)
        for n in mlp_names:
            W[n] = _effective(blocks["mlp"][n]["w"][li], maps[n])
        W["ln1"] = _f32({k: a[li] for k, a in blocks["ln1"].items()})
        W["ln2"] = _f32({k: a[li] for k, a in blocks["ln2"].items()})
        for v in variants:
            hs[v] = [_layer_jit(h, W, static, None if v == "ref" else v)
                     for h in hs[v]]
        del W
    head = _effective(raw["head"]["w"], bitmap(raw["head"]["w"][None])
                      if sparse else None)
    fnorm = _f32(raw["final_norm"])
    out = {v: [] for v in variants}
    for i, (st, srv) in enumerate(zip(starts, served)):
        srv = np.asarray(srv, np.int32)
        acc = {v: [] for v in variants}
        last = hs["ref"][i].shape[0] - 1
        for r0 in range(0, len(srv), HEAD_ROWS):
            n = min(len(srv), r0 + HEAD_ROWS) - r0
            # fixed-size row blocks (one compile); rows past n are dropped
            rows = jnp.minimum(st + r0 + jnp.arange(HEAD_ROWS), last)
            tok = np.zeros(HEAD_ROWS, np.int32)
            tok[:n] = srv[r0:r0 + n]
            ref = _head_jit(hs["ref"][i][rows], head, fnorm, kind, eps,
                            None)
            for v in variants:
                ctl = ref if v == "ref" else _head_jit(
                    hs[v][i][rows], head, fnorm, kind, eps, v)
                g_srv, g_ctl = _gaps(ref, ctl, jnp.asarray(tok))
                acc[v].append(np.asarray(g_srv if v == "ref" else g_ctl)[:n])
        for v in variants:
            out[v].append(np.concatenate(acc[v]))
    return out
