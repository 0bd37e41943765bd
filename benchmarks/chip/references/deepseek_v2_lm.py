"""Plain reference of a DeepSeek-V2 decoder (latent attention, MoE),
compressed as run.

Float32 at the highest matmul precision, one whole sequence at a time,
no cache, no kernels, no batching, no routing by sorting.  It follows
the configuration file and imports nothing of the program.  What it
computes:

* weights: the benchmark's seeded bf16 weights (``weights.py``), each
  linear quantised per output column to int4 (scale = max(amax / 7,
  1e-12), codes = clip(round(w / scale), -7, 7)); an expert stack per
  expert and column; the router too (the file's ``policy`` is ``quant``
  for every leaf).
* latent attention in the expanded form, as the model's code writes it:
  q = x Wq, per head [q_nope ; q_pe]; [c ; k_pe] = x Wkv_a,
  c = RMSNorm(c); rope on interleaved pairs of q_pe and k_pe with the
  YaRN frequencies of ``rope_scaling``; unless the KV cache is
  ``float``, the row [c ; k_pe] of each position quantised to int4 over
  its whole width (one scale a position, as the cache stores it);
  [k_nope ; v] = c Wkv_b per head, k = [k_nope ; k_pe]; causal softmax
  with scale (dn + dr)^-1/2 * m^2, m = 0.1 * mscale_all_dim * ln(factor)
  + 1; o = concat_h(attn_h) Wo.
* MLP: the first ``first_k_dense_replace`` layers silu(x Wg) * (x Wu)
  then Wd; the others a softmax over the router's logits, the
  ``num_experts_per_tok`` largest gates (renormalised only where
  ``norm_topk_prob``), times ``routed_scaling_factor``, each expert's
  SwiGLU weighted by its gate and summed (every expert computed on every
  row, gate zero where not chosen), plus the shared SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``.
* RMSNorm with the file's eps everywhere, then the head.

``variants`` may add the control: the same reference with the input of
every weight matmul one precision step below the configuration's bf16,
``"fp8"`` (float8 e4m3).
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
    """Per output column (axis -2 reduced), for (K, N) or (E, K, N)."""
    w = w.astype(jnp.float32)
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True) / qmax,
                        1e-12)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _act_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


LOWER = {"fp8": _act_fp8}


def _rows_int4(x):
    """(S, W): per position symmetric int4 over the whole row."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 7.0,
                        1e-12)
    return jnp.clip(jnp.round(x / scale), -7, 7) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """Hugging Face's ``_compute_yarn_parameters`` inverse frequencies."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / pos, 1.0 / (rs["factor"] * pos)

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _rope_pairs(x, inv):
    """x (S, ..., d), positions 0..S-1; pairs (x[2i], x[2i+1]) rotated as
    complex numbers."""
    S = x.shape[0]
    ang = jnp.arange(S, dtype=jnp.float32).reshape(
        (S,) + (1,) * (x.ndim - 2) + (1,)) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    p = x.reshape(*x.shape[:-1], -1, 2)
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def _attention(x, W, inv, st, lin):
    eps, H, r, dn, dr, dv, scale, kv_int4 = st[:8]
    S = x.shape[0]
    q = lin(x, W["wq"]).reshape(S, H, dn + dr)
    kv = lin(x, W["wkv_a"])
    c = _rms(kv[:, :r], W["kv_norm"], eps)
    q_pe = _rope_pairs(q[..., dn:], inv)
    k_pe = _rope_pairs(kv[:, r:], inv)
    if kv_int4:
        row = _rows_int4(jnp.concatenate([c, k_pe], -1))
        c, k_pe = row[:, :r], row[:, r:]
    kvb = lin(c, W["wkv_b"]).reshape(S, H, dn + dv)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe[:, None], (S, H, dr))], -1)
    v = kvb[..., dn:]
    qf = jnp.concatenate([q[..., :dn], q_pe], -1) * scale
    nb = S // Q_BLOCK

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(one, (jnp.arange(nb),
                          qf.reshape(nb, Q_BLOCK, H, dn + dr)))
    return lin(o.reshape(S, H * dv), W["wo"])


def _swiglu(x, wg, wu, wd, lin):
    return lin(jax.nn.silu(lin(x, wg)) * lin(x, wu), wd)


def _moe(x, W, st, lin):
    top_k, norm_topk, rsf = st[8:11]
    S = x.shape[0]
    gates = jax.nn.softmax(lin(x, W["router"]), -1)
    val, idx = jax.lax.top_k(gates, top_k)
    if norm_topk:
        val = val / val.sum(-1, keepdims=True)
    gate = jnp.zeros_like(gates).at[jnp.arange(S)[:, None], idx].set(
        val * rsf)                                         # (S, E)

    def expert(y, args):
        wg, wu, wd, ge = args
        e = _swiglu(x, _quant_cols(wg), _quant_cols(wu), _quant_cols(wd),
                    lin)
        return y + ge[:, None] * e, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (W["eg"], W["eu"], W["ed"], gate.T))
    return y + _swiglu(x, W["sg"], W["su"], W["sd"], lin)


def _layer(h, W, inv, st, lower):
    eps = st[0]
    lin = (lambda x, w: _mm(LOWER[lower](x), w)) if lower else _mm
    h = h + _attention(_rms(h, W["ln1"], eps), W, inv, st, lin)
    x = _rms(h, W["ln2"], eps)
    if "router" in W:
        return h + _moe(x, W, st, lin)
    return h + _swiglu(x, W["wg"], W["wu"], W["wd"], lin)


_layer_jit = jax.jit(_layer, static_argnums=(3, 4))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def _head_block(h, head, g, eps, lower):
    x = _rms(h, g, eps)
    return _mm(LOWER[lower](x) if lower else x, head)


_head_jit = jax.jit(_head_block, static_argnums=(3, 4))


@jax.jit
def _gaps(ref, ctl, served):
    best = ref.max(-1)
    g_srv = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    pick = jnp.argmax(ctl, -1)
    g_ctl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return g_srv, g_ctl


@jax.jit
def _q(w):
    return _quant_cols(w)


def _static(cfg: dict):
    rs = cfg["rope_scaling"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    scale = (dn + dr) ** -0.5 * _mscale(rs["factor"],
                                        rs["mscale_all_dim"]) ** 2
    st = (float(cfg["rms_norm_eps"]), cfg["num_attention_heads"],
          cfg["kv_lora_rank"], dn, dr, cfg["v_head_dim"], float(scale),
          cfg["serve"]["kv_cache"] != "float", cfg["num_experts_per_tok"],
          bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]))
    inv = jnp.asarray(yarn_inv_freq(dr, float(cfg["rope_theta"]), rs))
    return st, inv


def _layers(raw: dict):
    """(group tree, layer index) of every layer, in order."""
    out = []
    for name in ("dense_blocks", "blocks"):
        if name in raw:
            n = raw[name]["ln1"]["g"].shape[0]
            out += [(raw[name], i) for i in range(n)]
    return out


def _weights(tree, i):
    a, m = tree["attn"], tree.get("moe")
    f32 = jnp.float32
    W = {k: _q(a[k]["w"][i]) for k in ("wq", "wkv_a", "wkv_b", "wo")}
    W["kv_norm"] = a["kv_norm"]["g"][i].astype(f32)
    W["ln1"] = tree["ln1"]["g"][i].astype(f32)
    W["ln2"] = tree["ln2"]["g"][i].astype(f32)
    if m is None:
        W.update({k: _q(tree["mlp"][k]["w"][i]) for k in ("wg", "wu", "wd")})
    else:
        W["router"] = _q(m["router"]["w"][i])
        W.update({k: m[k]["w"][i] for k in ("eg", "eu", "ed")})
        W.update({"s" + k[1]: _q(m["shared"][k]["w"][i])
                  for k in ("wg", "wu", "wd")})
    return W


def hidden(raw: dict, cfg: dict, seqs: Sequence[np.ndarray],
           variants=("ref",)) -> Dict[str, List[jnp.ndarray]]:
    """Last hidden states (before the final norm) of each sequence, padded
    to a multiple of ``Q_BLOCK`` rows, under each variant."""
    st, inv = _static(cfg)
    hs = {v: [] for v in variants}
    for s in seqs:
        P = -(-len(s) // Q_BLOCK) * Q_BLOCK
        tok = jnp.asarray(np.pad(np.asarray(s, np.int32), (0, P - len(s))))
        e = _embed(raw["embed"]["w"], tok)
        for v in variants:
            hs[v].append(e)
    for tree, i in _layers(raw):
        W = _weights(tree, i)
        for v in variants:
            hs[v] = [_layer_jit(h, W, inv, st, None if v == "ref" else v)
                     for h in hs[v]]
        del W
    return hs


def logits(raw: dict, cfg: dict, seq: np.ndarray, variant: str = "ref"):
    """(len(seq), V) f32 logits of one sequence, the full forward."""
    h = hidden(raw, cfg, [seq], (variant,))[variant][0][:len(seq)]
    return _head_jit(h, _q(raw["head"]["w"]),
                     raw["final_norm"]["g"].astype(jnp.float32),
                     float(cfg["rms_norm_eps"]),
                     None if variant == "ref" else variant)


def gaps(raw: dict, cfg: dict, seqs: Sequence[np.ndarray],
         starts: Sequence[int], served: Sequence[np.ndarray],
         variants=("ref",)) -> Dict[str, List[np.ndarray]]:
    """Gap readings over served tokens, as ``dense_lm.gaps``: per
    sequence, the reference's gap of each served token (``"ref"``) and,
    where ``variants`` holds ``"fp8"``, the gap of the control's pick."""
    hs = hidden(raw, cfg, seqs, variants)
    head = _q(raw["head"]["w"])
    g = raw["final_norm"]["g"].astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    out = {v: [] for v in variants}
    for i, (st, srv) in enumerate(zip(starts, served)):
        srv = np.asarray(srv, np.int32)
        acc = {v: [] for v in variants}
        last = hs["ref"][i].shape[0] - 1
        for r0 in range(0, len(srv), HEAD_ROWS):
            n = min(len(srv), r0 + HEAD_ROWS) - r0
            rows = jnp.minimum(st + r0 + jnp.arange(HEAD_ROWS), last)
            tok = np.zeros(HEAD_ROWS, np.int32)
            tok[:n] = srv[r0:r0 + n]
            ref = _head_jit(hs["ref"][i][rows], head, g, eps, None)
            for v in variants:
                ctl = ref if v == "ref" else _head_jit(
                    hs[v][i][rows], head, g, eps, v)
                g_srv, g_ctl = _gaps(ref, ctl, jnp.asarray(tok))
                acc[v].append(np.asarray(g_srv if v == "ref" else g_ctl)[:n])
        for v in variants:
            out[v].append(np.concatenate(acc[v]))
    return out
