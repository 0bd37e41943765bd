"""Fused FC-stack kernel: a chain of dense linears in one launch.

The layer-fusion analogue of HPIPE's layer pipelining for the engine-free
datapath: adjacent compiled linears (LeNet's fc1→fc2→fc3) execute as ONE
Pallas kernel over a shared (bm, ·) row tile — every intermediate
activation lives in registers/VMEM for the lifetime of the tile and never
round-trips HBM between layers.

The weights arrive *dense f32* (trace-time decompressed/dequantised from
whatever container the layer compiled to — the dispatcher owns that
lowering): the stack is fused for memory locality, and for the small FC
shapes this targets, whole (K, N) weights fit VMEM comfortably.  Each
layer applies the shared fused bias+activation epilogue formula
(:data:`repro.kernels.sparse_matmul.kernel.ACTIVATIONS`) in f32 before
feeding the next, so the result matches the per-layer dispatch chain to
float tolerance (summation order inside a layer may differ from a sparse
container's block-ordered accumulation).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sparse_matmul.kernel import (ACTIVATIONS, _check_activation,
                                   _pad_rows, apply_activation)

__all__ = ["fc_stack_matmul", "fc_stack_vmem_bytes"]


def fc_stack_vmem_bytes(dims: Sequence[Tuple[int, int]],
                        bm: int = 128) -> int:
    """VMEM one fused-stack step claims: the double-buffered (bm, K1)
    input, every whole (K, N) f32 weight and (1, N) bias block (padded to
    8 sublanes), the (bm, N_L) output, plus one (bm, N) f32 intermediate
    per layer."""
    K1, n_out = dims[0][0], dims[-1][1]
    weights = sum(K * N * 4 + 8 * N * 4 for K, N in dims)
    inter = sum(bm * N * 4 for _, N in dims)
    return 2 * (bm * K1 * 4 + weights + bm * n_out * 4) + inter


def _stack_kernel(*refs, n_layers: int, activations):
    # refs: x, (w, b) * n_layers, o
    x_ref = refs[0]
    o_ref = refs[1 + 2 * n_layers]
    h = x_ref[...].astype(jnp.float32)
    for i in range(n_layers):
        w = refs[1 + 2 * i][...].astype(jnp.float32)
        b = refs[2 + 2 * i][0].astype(jnp.float32)
        h = jnp.dot(h, w, preferred_element_type=jnp.float32) + b[None, :]
        act = activations[i]
        if act is not None:
            h = apply_activation(h, act)
    o_ref[...] = h.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activations", "bm", "interpret", "out_dtype"),
)
def _call(x, weights, biases, *, activations, bm, interpret, out_dtype):
    M = x.shape[0]
    n_layers = len(weights)
    N_out = weights[-1].shape[1]
    in_specs = [pl.BlockSpec((bm, x.shape[1]), lambda m: (m, 0))]
    args = [x]
    for w, b in zip(weights, biases):
        K, N = w.shape
        in_specs.append(pl.BlockSpec((K, N), lambda m: (0, 0)))
        in_specs.append(pl.BlockSpec((1, N), lambda m: (0, 0)))
        args.append(w)
        args.append(b.reshape(1, N))
    return pl.pallas_call(
        functools.partial(_stack_kernel, n_layers=n_layers,
                          activations=activations),
        grid=(M // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, N_out), lambda m: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N_out), out_dtype),
        interpret=interpret,
        name="logicsparse_fc_stack",
    )(*args)


def fc_stack_matmul(
    x: jnp.ndarray,
    weights: Sequence[jnp.ndarray],
    biases: Sequence[Optional[jnp.ndarray]],
    activations: Sequence[Optional[str]],
    *,
    bm: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """y = actL(... act1(x @ W1 + b1) ... @ WL + bL), one kernel launch.

    ``x`` may be (..., K1); leading dims flatten to M and are padded to
    the row tile.  ``weights[i]`` is dense (K_i, N_i) with
    N_i == K_{i+1}; ``biases[i]`` is (N_i,) or None; ``activations[i]``
    is an :data:`ACTIVATIONS` name or None (applied after layer i).
    """
    if not weights or not (len(weights) == len(biases) == len(activations)):
        raise ValueError(
            f"fc_stack_matmul needs matching non-empty weights/biases/"
            f"activations, got lengths {len(weights)}/{len(biases)}/"
            f"{len(activations)}")
    for act in activations:
        _check_activation(act)
    dims = [tuple(map(int, w.shape)) for w in weights]
    K1 = dims[0][0]
    for (k_prev, n_prev), (k_next, _) in zip(dims, dims[1:]):
        if n_prev != k_next:
            raise ValueError(
                f"fc_stack_matmul chain mismatch: layer output {n_prev} "
                f"feeds layer input {k_next}")
    if x.shape[-1] != K1:
        raise ValueError(
            f"fc_stack_matmul: activation feature dim {x.shape[-1]} does "
            f"not match the first layer's K={K1}")
    lead = x.shape[:-1]
    xm = x.reshape(-1, K1)
    xm, M = _pad_rows(xm, bm)
    ws = tuple(jnp.asarray(w, jnp.float32) for w in weights)
    bs = tuple(
        jnp.zeros((n,), jnp.float32) if b is None
        else jnp.asarray(b, jnp.float32).reshape(n)
        for (_, n), b in zip(dims, biases))
    y = _call(xm, ws, bs, activations=tuple(activations), bm=bm,
              interpret=interpret, out_dtype=out_dtype)[:M]
    return y.reshape(*lead, dims[-1][1])
