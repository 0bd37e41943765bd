"""Test helpers for the packed KV cache: the stored layout built from
per-head values, and a plain f64 softmax oracle over those values."""
import jax.numpy as jnp
import numpy as np

from repro.core.quant import unpack_int4
from repro.kernels.flash_attention.decode_packed import scale_rows


def stored_layout(packed, scales):
    """K or V of L layers in the per-head layout — packed codes (L, B, T,
    Hkv, Dh/2), scales (L, B, T, Hkv) — in the cache's stored layout: a
    row's heads side by side (L, B, T, Hkv * Dh/2), the scales
    head-major (L, B, scale_rows(Hkv), T)."""
    L, B, T, Hkv = scales.shape
    rows = jnp.zeros((L, B, scale_rows(Hkv), T), jnp.float32)
    rows = rows.at[:, :, :Hkv].set(jnp.transpose(scales, (0, 1, 3, 2)))
    return packed.reshape(L, B, T, -1), rows


def softmax_oracle(q, k_p, v_p, k_s, v_s, lengths):
    """Plain softmax attention, numpy f64, over one layer's dequantised
    per-head K/V: packed codes (B, T, Hkv, Dh/2), scales (B, T, Hkv)."""
    B, _, H, Dh = q.shape
    Hkv = k_p.shape[2]
    G = H // Hkv
    k = np.asarray(unpack_int4(k_p, Dh, axis=-1), np.float64) \
        * np.asarray(k_s, np.float64)[..., None]
    v = np.asarray(unpack_int4(v_p, Dh, axis=-1), np.float64) \
        * np.asarray(v_s, np.float64)[..., None]
    out = np.zeros((B, 1, H, Dh))
    for b in range(B):
        n = int(lengths[b])
        for h in range(H):
            s = k[b, :n, h // G] @ np.asarray(q[b, 0, h], np.float64)
            s = s / np.sqrt(Dh)
            p = np.exp(s - s.max())
            out[b, 0, h] = (p / p.sum()) @ v[b, :n, h // G]
    return out
