"""Seeded random weights for a configuration, made on the device.

The benchmark makes the weights, not the program: the reference can then
make the very same values from the same seed without taking anything the
program produced.  The tree's structure (leaf paths, shapes, dtypes) is
given by the caller; every leaf is drawn from a key folded from the seed
and the leaf's path, all in one jitted call.

Linear weights are normal with std 1/sqrt(K), scaled per (bk, bn) block:
the ``ceil(density * blocks)`` strong blocks of each matrix (one random
set of positions, shared by every layer of a stack) get factors in
[1.0, 1.5] and the others factors in [0.5, 0.9].  The strong positions
are drawn from the leaf's path alone, not from the seed: the compressed
model's shapes (a block-sparse stack holds the union of its leaves'
patterns) are then the same for every seed, so every seed streams the
same blocks' worth of work and a run compiles nothing that an earlier
run of the cell has compiled.  Block pruning then has
a decision with a 10% margin, so which blocks survive does not hinge on
float summation order, and a magnitude ranking that picks the wrong
blocks shows.  In each output column, the largest weight that survives
pruning (every weight of a ``quant`` matrix, the strong blocks of a
``sparse`` one) is then set to ``7 * 2**j``, the smallest such value at
least as large: the column's int4 scale, amax / 7, is a power of two, so
``w / scale`` is exact in every arithmetic and its round-half-even ties
(frequent with bf16 weights) break the same way in the program and in the
reference.  Embeddings are standard normal, norm gains 1 + 0.1 N, biases
and norm shifts 0.1 N.
"""
from __future__ import annotations

import math
import time
import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

STRONG = (1.0, 1.5)
WEAK = (0.5, 0.9)
POSITIONS_SEED = 0     # strong-block positions come from this, not --seed


def base_key(seed: int):
    """A PRNG key from any whole number (64-bit seeds included)."""
    s = int(seed) % (1 << 64)
    k = jax.random.fold_in(jax.random.PRNGKey(0), s & 0xFFFFFFFF)
    return jax.random.fold_in(k, s >> 32)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def block_factors(pos_key, key, K: int, N: int, block: Tuple[int, int],
                  density: float):
    """(K//bk, N//bn) factors, strong on ceil(density * blocks) positions
    drawn from ``pos_key``; the factors' values from ``key``."""
    bk, bn = block
    nb = (K // bk) * (N // bn)
    n_strong = max(1, math.ceil(density * nb))
    ks, kw = jax.random.split(key)
    rank = jnp.argsort(jax.random.uniform(pos_key, (nb,)))
    strong = (rank < n_strong).reshape(K // bk, N // bn)
    f_s = jax.random.uniform(ks, strong.shape, minval=STRONG[0],
                             maxval=STRONG[1])
    f_w = jax.random.uniform(kw, strong.shape, minval=WEAK[0],
                             maxval=WEAK[1])
    return jnp.where(strong, f_s, f_w)


def _power_of_two_scales(x, keep):
    """Set each column's largest kept |weight| to 7 * 2**j (>= it)."""
    m = jnp.where(keep, jnp.abs(x), 0.0)
    cmax = jnp.max(m, axis=-2, keepdims=True)
    a = 7.0 * jnp.exp2(jnp.ceil(jnp.log2(cmax / 7.0)))
    top = jnp.arange(x.shape[-2])[:, None] == jnp.argmax(m, axis=-2)[
        ..., None, :]
    return jnp.where(top, jnp.where(x < 0, -a, a), x)


def draw_leaf(key, path: str, shape: Sequence[int], dtype,
              block: Tuple[int, int], density: float, policy: str):
    """One leaf's values; ``path`` is '/'-joined dict keys, ``policy`` the
    compression its linears get (``sparse`` or ``quant``)."""
    parts = path.split("/")
    name, parent = parts[-1], (parts[-2] if len(parts) > 1 else "")
    k = _leaf_key(key, path)
    shape = tuple(int(s) for s in shape)
    if name == "g":
        x = 1.0 + 0.1 * jax.random.normal(k, shape)
    elif name == "b":
        x = 0.1 * jax.random.normal(k, shape)
    elif name == "w" and parent == "embed":
        x = jax.random.normal(k, shape)
    elif name == "w":
        K, N = shape[-2:]
        kv, kf = jax.random.split(k)
        x = jax.random.normal(kv, shape) / math.sqrt(K)
        bk, bn = block
        keep = jnp.ones((K, N), bool)
        if K % bk == 0 and N % bn == 0:
            pos_key = _leaf_key(jax.random.PRNGKey(POSITIONS_SEED), path)
            f = block_factors(pos_key, kf, K, N, block, density)
            keep = jnp.repeat(jnp.repeat(f >= STRONG[0], bk, axis=0), bn,
                              axis=1)
            x = x * jnp.repeat(jnp.repeat(f, bk, axis=0), bn, axis=1)
        x = _power_of_two_scales(x, keep if policy == "sparse" else True)
    else:
        raise ValueError(f"no rule to draw leaf {path!r}")
    return x.astype(dtype)


def tree_paths(tree, prefix: str = "") -> Dict[str, object]:
    """Flatten a dict-of-dicts to {'a/b/c': leaf}."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(tree_paths(v, p))
        else:
            out[p] = v
    return out


def unflatten(flat: Dict[str, object]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def make(seed: int, shapes: dict, block: Tuple[int, int], density: float,
         policy: str):
    """The whole tree, on the default device, in one jitted call.

    ``shapes`` is a dict tree of objects with ``shape`` and ``dtype``
    (``jax.ShapeDtypeStruct``, as ``jax.eval_shape`` gives)."""
    flat = tree_paths(shapes)
    spec = tuple((p, tuple(s.shape), jnp.dtype(s.dtype))
                 for p, s in sorted(flat.items()))

    def build(key):
        return {p: draw_leaf(key, p, shp, dt, block, density, policy)
                for p, shp, dt in spec}

    key = base_key(seed)
    t0 = time.perf_counter()
    compiled = jax.jit(build).lower(key).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(key))
    print(f"weights: lowered and compiled in {t1 - t0:.3f} s, drawn in "
          f"{time.perf_counter() - t1:.3f} s", flush=True)
    return unflatten(out)


