"""Whole-model compression pass — one canonical compressed representation.

``compile_model`` (transformer pytrees) and ``compile_lenet`` (the paper's
Table-1 workload) take trained params + per-layer masks (from
:func:`repro.core.pruning.block_aware_prune`) + quant scales (from
:mod:`repro.core.quant`) and lower every eligible layer — linear *and*
convolution — onto the engine-free datapath:

* ``dense``  — weight kept as-is (small / awkward shapes);
* ``quant``  — int8 storage with per-output-channel scales, executed by the
  fused-dequant matmul (``{"w_q", "w_s"}`` leaves / :class:`QuantizedTensor`);
* ``sparse`` — compile-time block-compacted, optionally int8, executed by
  the static-schedule Pallas kernel or its XLA static-gather twin
  (``{"w_blk"[, "w_s"]}`` leaves / :class:`CompressedLinear`).

The per-layer policy is chosen by a roofline heuristic over
:mod:`repro.core.cost_model` (decode-shaped by default: weight streaming
dominates, so eliminated blocks pay off immediately).

Every **4-bit** leaf — quant and quantised-sparse, linear and conv alike —
is emitted in a *bit-packed* storage container (two int4 codes per uint8
byte; :class:`repro.core.quant.PackedTensor` payloads, ``w_qp``/``w_blkp``
pytree leaves), so the bytes actually held in memory match the stored-bits
accounting instead of paying an int8 container per code.  Execution is
bitwise identical to the int8 containers: the kernels decode the nibbles
in-register, the jnp twins unpack at trace time.  ``LayerReport`` carries
both accountings (``compressed_bytes`` = int8-container baseline,
``container_bytes`` = realised), and ``CompressedModel.byte_compression``
is the honest byte-level ratio.

Convolutions are *the same thing*: a ``(kh, kw, cin, cout)`` conv weight
is reshaped (statically, at compile time) to the ``(K = cin*kh*kw, N =
cout)`` im2col matrix — in the patch-feature order of
``lax.conv_general_dilated_patches`` — and runs through the identical
shared-pattern / compress / quantize pipeline.  The resulting payload is
wrapped in :class:`repro.core.dispatch.ConvPayload` (payload + static conv
geometry) and executed by ``conv_dispatch``: im2col at trace time, then
the very same sparse/quant kernels the FC layers use.  The policy pick is
conv-aware — a conv leaf's MACs scale by its output H·W (its reuse of the
streamed weight), which is exactly what its LayerSpec encodes.

Representation invariant (what makes this pass composable with scan /
sharding): **one BlockSparsePattern per (K, N) linear shape**, shared by
every layer of the stack.  Stacked parameter leaves stay stackable —
``w_blk`` is (L, P, bk, bn) — so the 126-layer While-loop lowering and the
serving engine's jitted ``decode_step`` consume the compacted format
directly.  The shared bitmap is scored by block L1 mass *summed across the
stack*; inside surviving blocks each layer keeps its own unstructured
element mask (free at runtime, counted in nnz).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from . import payload_registry
from .cost_model import (
    HWSpec,
    LayerSpec,
    decode_linear_spec,
    device_hw,
    layer_latency,
)
from .dispatch import ConvPayload, conv_out_hw
from .folding import FoldingConfig
from .sparsity import (
    BlockSparsePattern,
    pattern_from_bitmap,
    pattern_from_mask,
)

__all__ = [
    "CompileRules",
    "LayerReport",
    "CompressedModel",
    "choose_policy",
    "compile_policies",
    "compile_model",
    "compile_conv",
    "compile_lenet",
    "conv_weight_matrix",
    "conv_weight_unmatrix",
    "decompress_model",
    "realised_densities",
]


def compile_policies() -> Tuple[str, ...]:
    """Valid per-layer policies: ``"dense"`` (keep the weight, optionally
    masked — no payload family) plus every registered policy compiler
    (:func:`repro.core.payload_registry.register_policy`): "quant",
    "sparse", "perchannel", ...  Registering a new family's compiler makes
    its name a valid override here with no edits to this module."""
    return ("dense",) + payload_registry.policy_names()


# accepted as an *override* value on top of compile_policies(): defer the
# pick (and the quant bit-width, {16, 8, 4}) to the autotuner's
# network_estimate re-ranking instead of the fixed choose_policy heuristic
AUTOTUNE_POLICY = "autotune"

# Stacked transformer linear leaves the pass may rewrite.  SSM/Mamba blocks
# reuse some of these names but apply them without a pattern table, so the
# walk below only descends into attention/MLP/MoE subtrees (see
# _iter_linears).  ``wkv_a``/``wkv_b`` are latent attention's (MLA) down
# and up projections; ``router`` and the routed expert stacks
# ``eg``/``eu``/``ed`` ((L, E, K, N)) are a MoE layer's.
_LINEAR_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "wkv_a", "wkv_b",
                "router", "eg", "eu", "ed")
_LINEAR_SUBTREES = ("attn", "mlp", "shared", "moe")
# a MoE layer's data-dependent leaves: no block elimination (a pruned
# router or expert has no static schedule), quant or dense only
_ROUTED_KEYS = ("router", "eg", "eu", "ed")
_BLOCK_ROOTS = ("dense_blocks", "blocks")


@dataclasses.dataclass(frozen=True)
class CompileRules:
    """Knobs of the compression pass (all compile-time).

    The same rules govern linear and conv leaves: a conv's ``block`` /
    ``policies`` / ``masks`` entries apply to its im2col matrix
    ``(cin*kh*kw, cout)``.  Conv masks may be given kernel-shaped
    ``(kh, kw, cin, cout)`` (as produced by pruning the raw weight) or
    already im2col-shaped ``(K, N)`` — both are accepted.
    """

    block: Tuple[int, int] = (128, 128)   # clipped per-shape to (K, N)
    quant_bits: int = 8
    block_density: float = 0.25           # target when deriving masks
    in_block_density: float = 1.0         # unstructured level inside blocks
    batch_tokens: int = 1                 # cost-model shape (decode default)
    hw: Optional[HWSpec] = None           # None = this device's spec
    min_weight_elems: int = 4096          # below this: always dense
    quantize_sparse: bool = True          # sparse blocks stored int8
    dtype: Any = jnp.float32              # float storage dtype (non-quant)
    policies: Optional[Dict[str, str]] = None  # per-leaf-name override
    # threshold captured into the "actsparse" family: a following ReLU is
    # sharpened to trelu(y, tau) so small positives become exact zeros
    act_threshold: float = 0.0


@dataclasses.dataclass
class LayerReport:
    name: str
    policy: str
    shape: Tuple[int, int]       # im2col (K, N) for conv leaves
    n_layers: int
    dense_bytes: int
    compressed_bytes: int        # int8-container accounting (codes + scales)
    block_density: float
    element_density: float
    kind: str = "linear"         # "linear" | "conv" | "expert" (L*E stack)
    m_scale: int = 1             # matmul rows per batch row (conv: H_out*W_out)
    # bytes the payload actually holds in memory: equals compressed_bytes
    # except for bit-packed 4-bit leaves, whose uint8 containers hold two
    # codes per byte (None = same as compressed_bytes)
    container_bytes: Optional[int] = None

    @property
    def realised_bytes(self) -> int:
        return self.compressed_bytes if self.container_bytes is None \
            else self.container_bytes


@dataclasses.dataclass
class CompressedModel:
    """The canonical compressed-parameter representation.

    ``params`` is consumed directly by ``models.model.forward`` /
    ``decode_step`` (transformers) or ``models.lenet.lenet_forward`` via
    ``layers`` (LeNet-style per-name payloads).  ``patterns`` is the static
    side-table: (K, N) -> BlockSparsePattern, passed to the model at trace
    time (compile-time constants, never traced).
    """

    params: Any
    patterns: Dict[Tuple[int, int], BlockSparsePattern]
    report: List[LayerReport]
    layers: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Layer-fusion plan derived at compile time (e.g. lenet_fusion_plan):
    # which compressed leaves may run fused schedules (in-kernel pool,
    # fc-stack chaining).  Consumers opt in by passing it to the model's
    # forward (fusion=cm.fusion); empty dict = no fusion opportunities.
    fusion: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def storage_bytes(self) -> int:
        """Payload bytes of every layer plus each shared schedule's static
        metadata exactly once — patterns are shared across same-shape
        leaves, so their bitmap/coord bytes are model-level, not
        per-leaf (LayerReport.compressed_bytes is payload-only for
        sparse layers).  This is the *int8-container* accounting: one
        byte per stored code regardless of bit-packing — the baseline the
        byte-level (container) accounting is compared against."""
        return sum(r.compressed_bytes for r in self.report) \
            + sum(p.meta_bytes for p in self.patterns.values())

    @property
    def container_storage_bytes(self) -> int:
        """Bytes the compiled model actually holds in memory: bit-packed
        4-bit leaves count their uint8 containers (two codes per byte),
        everything else equals the int8-container accounting."""
        return sum(r.realised_bytes for r in self.report) \
            + sum(p.meta_bytes for p in self.patterns.values())

    @property
    def dense_bytes(self) -> int:
        return sum(r.dense_bytes for r in self.report)

    @property
    def compression(self) -> float:
        """dense fp32 bytes / int8-container bytes (the pre-packing
        baseline ratio; see :attr:`byte_compression` for realised bytes)."""
        return self.dense_bytes / max(1, self.storage_bytes)

    @property
    def byte_compression(self) -> float:
        """dense fp32 bytes / bytes actually held — the honest byte-level
        ratio the paper's storage claim is judged against.  Equal to
        :attr:`compression` when nothing is bit-packed."""
        return self.dense_bytes / max(1, self.container_storage_bytes)

    def policy_of(self, name: str) -> str:
        for r in self.report:
            if r.name == name:
                return r.policy
        raise KeyError(name)


# ------------------------------------------------------- conv <-> matrix


def conv_weight_matrix(w4):
    """(kh, kw, cin, cout) conv weight -> its (cin*kh*kw, cout) im2col
    matrix, in the patch-feature order of
    ``lax.conv_general_dilated_patches`` (channel major, then kh, kw).
    Works on numpy and jnp arrays (boolean masks included)."""
    kh, kw, cin, cout = w4.shape
    return w4.transpose(2, 0, 1, 3).reshape(cin * kh * kw, cout)


def conv_weight_unmatrix(w2, kernel: Tuple[int, int, int, int]):
    """Inverse of :func:`conv_weight_matrix`: (K, N) -> (kh, kw, cin, cout)."""
    kh, kw, cin, cout = kernel
    return w2.reshape(cin, kh, kw, cout).transpose(1, 2, 0, 3)


# ------------------------------------------------------------------ policy


def choose_policy(
    K: int,
    N: int,
    *,
    rules: CompileRules,
    block_density: float,
    element_density: float,
    sparse_eligible: bool,
    spec: Optional[LayerSpec] = None,
) -> str:
    """Roofline-based per-layer policy pick (cost_model heuristic).

    Builds a decode-shaped LayerSpec and compares the three datapaths'
    latencies; storage-floor gates keep tiny layers dense (metadata and
    kernel launch overheads dominate real wins there).  ``spec`` overrides
    the default linear-shaped LayerSpec — conv leaves pass their own
    (MACs scaled by output H·W, real activation traffic), so the compare
    sees the conv's weight reuse instead of pretending it is a decode
    linear.
    """
    if K * N < rules.min_weight_elems:
        return "dense"
    if spec is None:
        spec = decode_linear_spec(K, N, rules.batch_tokens)
    hw = rules.hw or device_hw()
    lat = {
        "dense": layer_latency(
            spec, FoldingConfig(parallelism=hw.lanes, unroll="factor",
                                quant_bits=16), hw)["total"],
        "quant": layer_latency(
            spec, FoldingConfig(parallelism=hw.lanes, unroll="factor",
                                quant_bits=rules.quant_bits), hw)["total"],
    }
    if sparse_eligible:
        lat["sparse"] = layer_latency(
            spec, FoldingConfig(parallelism=hw.lanes, unroll="sparse",
                                block_density=block_density,
                                element_density=element_density,
                                quant_bits=rules.quant_bits), hw)["total"]
    return min(lat, key=lat.get)


def _fit_block(K: int, N: int, block: Tuple[int, int]) -> Optional[Tuple[int, int]]:
    """Clip the rule block to the shape; None if it cannot tile (K, N)."""
    bk, bn = min(block[0], K), min(block[1], N)
    if bk < 1 or bn < 1 or K % bk or N % bn:
        return None
    return bk, bn


# ----------------------------------------------------- shared mask helpers


def _shared_bitmap(stack: np.ndarray, block: Tuple[int, int],
                   block_density: float) -> np.ndarray:
    """One block bitmap for a whole (L, K, N) stack: score by summed |w|."""
    L, K, N = stack.shape
    bk, bn = block
    score = np.abs(stack).reshape(L, K // bk, bk, N // bn, bn).sum(axis=(0, 2, 4))
    n_total = score.size
    n_keep = max(1, int(np.ceil(block_density * n_total)))
    flat = score.ravel()
    keep = np.argpartition(flat, n_total - n_keep)[n_total - n_keep:]
    bitmap = np.zeros(n_total, dtype=bool)
    bitmap[keep] = True
    return bitmap.reshape(score.shape)


def _element_mask(w: np.ndarray, bitmap: np.ndarray, block: Tuple[int, int],
                  in_block_density: float) -> np.ndarray:
    """Per-layer element mask under a fixed bitmap; >= 1 element survives in
    every present block so pattern_from_mask reproduces the shared bitmap."""
    K, N = w.shape
    bk, bn = block
    gb = w.reshape(K // bk, bk, N // bn, bn)
    if in_block_density >= 1.0:
        em = np.broadcast_to(bitmap[:, None, :, None], gb.shape)
        return em.reshape(K, N).copy()
    k_in = max(1, int(np.ceil(in_block_density * bk * bn)))
    m4 = np.zeros(gb.shape, dtype=bool)
    for r, c in zip(*np.nonzero(bitmap)):
        blk = np.abs(gb[r, :, c, :])
        thr = np.partition(blk.ravel(), blk.size - k_in)[blk.size - k_in]
        m4[r, :, c, :] = blk >= thr
    return m4.reshape(K, N)


def _mask_bitmap(mask: np.ndarray, block: Tuple[int, int]) -> np.ndarray:
    return pattern_from_mask(mask, block).bitmap


def _decide_policy(
    name: str,
    override: Optional[str],
    K: int,
    N: int,
    rules: CompileRules,
    *,
    block: Optional[Tuple[int, int]],
    block_density: float,
    element_density: float,
    spec: Optional[LayerSpec] = None,
) -> Tuple[str, int]:
    """Per-layer (policy, quant_bits) gate shared by compile_model and
    compile_lenet: explicit override, else cost model; the ``"autotune"``
    override defers both the policy and the bit-width to the tuner's
    network_estimate re-ranking; sparse downgrades to quant when the rule
    block cannot tile the shape.  ``spec`` carries conv-aware cost inputs
    (see :func:`choose_policy`)."""
    valid = compile_policies()
    if override is not None and override not in valid + (AUTOTUNE_POLICY,):
        raise ValueError(
            f"{name}: unknown policy {override!r} — valid: "
            f"{valid + (AUTOTUNE_POLICY,)}")
    if override is not None and block is None and \
            payload_registry.policy_eliminates_blocks(override):
        raise ValueError(
            f"{name}: policy {override!r} was explicitly requested but "
            f"block {rules.block} cannot tile shape {(K, N)} — pick a "
            "dividing block or drop the override")
    if override == AUTOTUNE_POLICY:
        from .autotune import tuned_policy
        return tuned_policy(
            K, N, rules=rules, block_density=block_density,
            element_density=element_density,
            sparse_eligible=block is not None, spec=spec)
    policy = override or choose_policy(
        K, N, rules=rules, block_density=block_density,
        element_density=element_density, sparse_eligible=block is not None,
        spec=spec)
    if policy == "sparse" and block is None:  # cost-model fallback only
        policy = "quant"
    return policy, rules.quant_bits


# --------------------------------------------------------- leaf compilers
#
# The per-policy leaf emission (quantise / block-compact / bit-pack, with
# both byte accountings) lives on the registered PolicyCompilers — see
# ``repro.core.families`` — so this pass only keeps the policy *skeleton*:
# masking, pattern union, report accounting.


@dataclasses.dataclass
class _LeafPlan:
    """Phase-A analysis of one linear leaf (see compile_model)."""

    path: str
    parent: dict
    key: str
    stack: np.ndarray            # (L, K, N) f32
    stacked: bool
    mask: Optional[np.ndarray]   # (L, K, N) bool or None
    block: Optional[Tuple[int, int]]
    bitmap: Optional[np.ndarray]  # this leaf's own block bitmap (sparse only)
    policy: str
    bits: int                    # quant storage bit-width for this leaf
    bd: float
    ed: float


# -------------------------------------------------------------- model pass


def _iter_linears(tree: Any, path: str = "", in_linear_subtree: bool = False):
    """Yield (path, parent_dict, key) for every (compiled or raw) linear.

    Membership is "holds any registered family's key leaf" — a dict is a
    linear leaf iff some payload family claims it, so new families are
    walked without this function learning their leaf names."""
    if not isinstance(tree, dict):
        return
    weight_leaves = payload_registry.weight_leaf_names()
    for k, v in tree.items():
        p = f"{path}/{k}" if path else k
        if (in_linear_subtree and k in _LINEAR_KEYS and isinstance(v, dict)
                and any(lk in v for lk in weight_leaves)):
            yield p, tree, k
        elif isinstance(v, dict):
            yield from _iter_linears(
                v, p, in_linear_subtree or k in _LINEAR_SUBTREES)


LANE = 128


# Linears whose consumer (:mod:`repro.models.blocks`) slices the output
# to its logical width: latent attention's down projection
# (576 = 512 + 64 columns in DeepSeek-V2-Lite) and the router (one column
# an expert, 64 there).
_SLICED_OUTPUTS = ("wkv_a", "router")


def _pad_leaf(leaf: dict, ax: int, pad: int, bias: bool) -> dict:
    leaf = dict(leaf)
    w = leaf["w"]
    widths = [(0, 0)] * w.ndim
    widths[ax] = (0, pad)
    leaf["w"] = jnp.pad(w, widths)
    if bias and "b" in leaf:
        b = leaf["b"]
        leaf["b"] = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    return leaf


def _pad_lane_widths(tree, path: str, rules: CompileRules) -> None:
    """Zero-pad widths that are not a multiple of ``LANE`` so that every
    kernel tile of the leaf is a whole number of lanes, in place on a
    copied spine, where the rules name a policy without block elimination
    (a block pattern is looked up by the logical shape):

    * a raw MLP's hidden width (DeepSeek-V2-Lite's dense 10,944): the
      gate/up columns and the down rows that are added are zero, so the
      MLP computes exactly what it did (act(0) * 0 = 0 feeds zero rows);
    * the output width of a :data:`_SLICED_OUTPUTS` linear, whose added
      zero columns the consumer slices off."""
    if not isinstance(tree, dict):
        return
    pols = rules.policies or {}

    def fixed(k):
        pol = pols.get(f"{path}/{k}", pols.get(k))
        return pol is not None and pol != AUTOTUNE_POLICY and \
            not payload_registry.policy_eliminates_blocks(pol)

    def raw(k):
        return isinstance(tree.get(k), dict) and "w" in tree[k]

    keys = [k for k in ("wg", "wu", "wd") if k in tree]
    if "wd" in keys and all(raw(k) for k in keys):
        F = int(tree["wd"]["w"].shape[-2])
        pad = -F % LANE
        if pad and all(fixed(k) for k in keys):
            for k in keys:
                tree[k] = _pad_leaf(tree[k], -2 if k == "wd" else -1, pad,
                                    bias=k != "wd")
        return
    for k, v in tree.items():
        if k in _SLICED_OUTPUTS and raw(k):
            pad = -int(v["w"].shape[-1]) % LANE
            if pad and fixed(k):
                tree[k] = _pad_leaf(v, -1, pad, bias=True)
        else:
            _pad_lane_widths(v, f"{path}/{k}", rules)


def _copy_spine(tree):
    """Copy the dict structure; array leaves are shared, never mutated."""
    if not isinstance(tree, dict):
        return tree
    return {k: _copy_spine(v) for k, v in tree.items()}


def _compile_experts(path: str, parent: dict, key: str, rules: CompileRules,
                     override: Optional[str], report: List[LayerReport],
                     mask) -> None:
    """Lower one (L, E, K, N) routed-expert stack in place and report it:
    ``quant`` on the device (no host round trip of the largest leaves),
    or ``dense`` kept as is."""
    from .families.quant import compile_expert_stack
    if mask is not None:
        raise ValueError(f"{path}: routed experts take no pruning mask")
    leaf = parent[key]
    w = leaf["w"]
    L, E, K, N = (int(d) for d in w.shape)
    dense_bytes = int(w.size * w.dtype.itemsize)
    policy, bits = _decide_policy(path, override, K, N, rules, block=None,
                                  block_density=1.0, element_density=1.0)
    if policy not in ("dense", "quant"):
        raise ValueError(f"{path}: routed experts compile under 'quant' or "
                         f"'dense', not {policy!r}")
    out = {k: v for k, v in leaf.items() if k != "w"}
    if policy == "dense":
        out["w"] = w
        comp = cont = dense_bytes
    else:
        leaves = compile_expert_stack(w, bits)
        out.update(leaves)
        n_s = L * E * N
        comp = L * E * K * N + 4 * n_s
        cont = sum(int(v.size * v.dtype.itemsize) for v in leaves.values())
    parent[key] = out
    report.append(LayerReport(
        name=path, policy=policy, shape=(K, N), n_layers=L * E,
        dense_bytes=dense_bytes, compressed_bytes=int(comp),
        block_density=1.0, element_density=1.0, kind="expert",
        container_bytes=int(cont)))


def compile_model(
    params: Any,
    cfg: Any,
    *,
    masks: Optional[Dict[str, np.ndarray]] = None,
    rules: CompileRules = CompileRules(),
) -> CompressedModel:
    """Lower a transformer parameter pytree onto the compressed datapath.

    ``cfg`` is the model's ArchConfig (only ``family`` is consulted).
    ``masks`` maps leaf names ("wq", ... or "head") to (L, K, N) / (K, N)
    boolean keep-masks; absent entries are derived by two-level pruning at
    ``rules.block_density`` x ``rules.in_block_density``.

    The result's ``params`` drop into ``forward`` / ``decode_step`` /
    ``ServeEngine`` together with ``patterns``.

    An MLP hidden width, or the output width of latent attention's down
    projection or the router, that is not a multiple of 128 lanes, and
    whose leaves the rules put under policies without block elimination,
    is first zero-padded to one (:func:`_pad_lane_widths`): the same
    function, and every kernel tile whole.

    Scope note: a MoE layer's router and routed-expert stacks
    (``eg``/``eu``/``ed``, (L, E, K, N)) are lowered like any linear,
    under ``quant`` or ``dense`` only: their rows are chosen at run time
    (dropless top-k), so a static block schedule does not apply.  An
    expert stack quantises on the device, per (expert, output column),
    into the int4x2 container the grouped expert kernel reads
    (:func:`repro.core.families.quant.compile_expert_stack`); its report
    row counts L x E matrices (``kind`` "expert").
    """
    if cfg.family not in ("dense", "encoder", "vlm", "moe", "hybrid"):
        raise NotImplementedError(
            f"compile_model supports attention/MLP families, got {cfg.family}")

    patterns: Dict[Tuple[int, int], BlockSparsePattern] = {}
    report: List[LayerReport] = []

    consumed_mask_keys = set()
    consumed_policy_keys = set()

    def _mask_for(path: str, leaf: str):
        """Masks may be keyed by full path ("blocks/attn/wq") or leaf name."""
        if not masks:
            return None
        key = path if path in masks else (leaf if leaf in masks else None)
        if key is None:
            return None
        consumed_mask_keys.add(key)
        m = np.asarray(masks[key], bool)
        return m if m.ndim == 3 else m[None]

    def _override_for(path: str, leaf: str):
        """Policy overrides accept the same keys as masks (path or leaf)."""
        pols = rules.policies
        if not pols:
            return None
        key = path if path in pols else (leaf if leaf in pols else None)
        if key is None:
            return None
        consumed_policy_keys.add(key)
        return pols[key]

    new_params = _copy_spine(params)
    for root_name in _BLOCK_ROOTS:
        _pad_lane_widths(new_params.get(root_name), root_name, rules)

    sites: List[Tuple[str, dict, str]] = []
    roots = [] if cfg.family == "hybrid" else [
        r for r in _BLOCK_ROOTS if r in params]
    if "shared_attn" in params:
        roots.append("shared_attn")
    for root_name in roots:
        sites.extend(_iter_linears(new_params[root_name], root_name))
    if isinstance(params.get("head"), dict) and any(
            lk in params["head"]
            for lk in payload_registry.weight_leaf_names()):
        sites.append(("head", new_params, "head"))

    # Phase A — analyze each leaf: policy + (for sparse) its own bitmap.
    plans: List[_LeafPlan] = []
    for path, parent, key in sites:
        leaf = parent[key]
        if "w" not in leaf:
            raise ValueError(
                f"{path}: leaf is already compiled ({sorted(leaf)}); "
                "compile_model expects a raw dense parameter tree — use "
                "decompress_model() first to recompile")
        if leaf["w"].ndim == 4:       # routed experts: lowered on the device
            _compile_experts(path, parent, key, rules,
                             _override_for(path, key), report,
                             _mask_for(path, key))
            continue
        w = np.asarray(leaf["w"], np.float32)
        stacked = w.ndim == 3
        stack = w if stacked else w[None]
        L, K, N = stack.shape
        mask = _mask_for(path, key)
        if mask is not None:
            if mask.shape[1:] != (K, N) or mask.shape[0] not in (1, L):
                raise ValueError(
                    f"{path}: mask shape {mask.shape} does not match "
                    f"weight stack {(L, K, N)}")
            if mask.shape[0] == 1 and L > 1:  # (K, N) mask: every layer
                mask = np.broadcast_to(mask, (L, K, N)).copy()
        block = None if key in _ROUTED_KEYS else _fit_block(K, N,
                                                                rules.block)
        bitmap = None
        if mask is not None and block is not None:
            bitmap = _mask_bitmap(mask[0], block)
            for ml in mask[1:]:
                bitmap |= _mask_bitmap(ml, block)
            bd = bitmap.sum() / bitmap.size
            ed = mask.sum() / mask.size
        else:
            bd = rules.block_density
            ed = rules.block_density * rules.in_block_density
        policy, bits = _decide_policy(path, _override_for(path, key), K, N,
                                      rules, block=block, block_density=bd,
                                      element_density=ed)
        if payload_registry.policy_eliminates_blocks(policy) and bitmap is None:
            bitmap = _shared_bitmap(stack, block, rules.block_density)
            bd = bitmap.sum() / bitmap.size
        plans.append(_LeafPlan(path, parent, key, stack, stacked, mask,
                               block, bitmap, policy, bits, float(bd),
                               float(ed)))

    valid = sorted(pl.path for pl in plans)
    unused = set(masks or {}) - consumed_mask_keys
    if unused:
        raise ValueError(
            f"masks keys matched no linear leaf: {sorted(unused)} — valid "
            f"keys are leaf names or full paths from {valid}; a typo here "
            "would silently drop pruning")
    unused = set(rules.policies or {}) - consumed_policy_keys
    if unused:
        raise ValueError(
            f"policies keys matched no linear leaf: {sorted(unused)} — "
            f"valid keys are leaf names or full paths from {valid}")

    # Phase B — one pattern per (K, N) shape: union of the leaf bitmaps.
    # Blocks a leaf's own mask never touches are packed as zero tiles, the
    # price of keeping stacked/scan-uniform leaves and a single schedule.
    for pl in plans:
        if not payload_registry.policy_eliminates_blocks(pl.policy):
            continue
        K, N = pl.stack.shape[1:]
        prev = patterns.get((K, N))
        if prev is None:
            patterns[(K, N)] = pattern_from_bitmap((K, N), pl.block,
                                                   pl.bitmap.copy())
        else:
            patterns[(K, N)] = pattern_from_bitmap(
                (K, N), pl.block, prev.bitmap | pl.bitmap)

    # Phase C — rewrite the leaves.
    for pl in plans:
        leaf = pl.parent[pl.key]
        L, K, N = pl.stack.shape
        dense_bytes = int(np.asarray(leaf["w"]).size
                          * np.asarray(leaf["w"]).dtype.itemsize)
        out = {k: v for k, v in leaf.items() if k != "w"}
        bd, ed = pl.bd, pl.ed
        # a user mask is honoured under EVERY policy: quant/dense layers
        # keep the pruned zeros (no silent weight resurrection), they just
        # don't get the block-compaction storage win
        masked_stack = pl.stack if pl.mask is None else pl.stack * pl.mask
        eliminates = payload_registry.policy_eliminates_blocks(pl.policy)
        if not eliminates:
            bd = 1.0  # no block elimination on these paths
            ed = 1.0 if pl.mask is None else pl.mask.sum() / pl.mask.size
        if pl.policy == "dense":
            if pl.mask is None:
                out["w"] = leaf["w"]
            else:
                w = masked_stack if pl.stacked else masked_stack[0]
                out["w"] = jnp.asarray(w, np.asarray(leaf["w"]).dtype)
            comp_bytes = cont_bytes = dense_bytes
        else:
            pc = payload_registry.policy_compiler(pl.policy)
            mask, pattern = pl.mask, None
            if eliminates:
                if mask is None:
                    mask = np.stack([
                        _element_mask(wl, pl.bitmap, pl.block,
                                      rules.in_block_density)
                        for wl in pl.stack])
                pattern = patterns[(K, N)]
            leaves, comp_bytes, cont_bytes, ed_r = pc.compile_stack(
                pl.stack, mask, pattern=pattern, bits=pl.bits, rules=rules)
            if ed_r is not None:
                ed = ed_r
            if pattern is not None:
                bd = pattern.block_density
            if not pl.stacked:
                leaves = {k: v[0] for k, v in leaves.items()}
            out.update(leaves)
        pl.parent[pl.key] = out
        report.append(LayerReport(
            name=pl.path, policy=pl.policy, shape=(K, N), n_layers=L,
            dense_bytes=dense_bytes, compressed_bytes=int(comp_bytes),
            block_density=float(bd), element_density=float(ed),
            container_bytes=int(cont_bytes)))

    if cfg.family == "hybrid":
        # the Mamba superblocks (bulk of a hybrid model) are not lowered —
        # account them as one aggregate dense row so compression is honest
        def _tree_bytes(t):
            if isinstance(t, dict):
                return sum(_tree_bytes(v) for v in t.values())
            a = np.asarray(t)
            return int(a.size * a.dtype.itemsize)

        b = _tree_bytes(params["blocks"])
        report.append(LayerReport(
            name="blocks (ssm, not lowered)", policy="dense", shape=(0, 0),
            n_layers=0, dense_bytes=b, compressed_bytes=b,
            block_density=1.0, element_density=1.0))

    return CompressedModel(params=new_params, patterns=patterns, report=report)


def _decompress_leaf(leaf: Dict[str, Any],
                     pattern: Optional[BlockSparsePattern], dtype,
                     shape: Optional[Tuple[int, int]] = None):
    """Reconstruct a plain-``w`` leaf via the owning family's decompress
    hook; leaves no family claims (or that have no hook) pass through."""
    fam = payload_registry.family_for_leaves(leaf)
    if fam is None or fam.decompress is None:
        return leaf
    return fam.decompress(leaf, pattern=pattern, shape=shape, dtype=dtype)


def decompress_model(cm: CompressedModel, *, dtype=jnp.float32) -> Any:
    """Dense oracle: reconstruct a plain-``w`` pytree from the compressed
    one (dequantised, blocks scattered back).  Differential tests run the
    model on this reconstruction and compare against the compacted path.

    For LeNet-style models (``cm.layers`` payloads) the reconstruction is
    the original param dict with each compressed ``<name>_w`` replaced by
    its dequantised / scattered dense weight.
    """
    if cm.layers:  # compile_lenet result: rebuild <name>_w from payloads
        def _payload_dense(payload):
            fam = payload_registry.family_of_payload(payload)
            if fam is None or fam.payload_dense is None:
                return jnp.asarray(payload, dtype)  # masked dense array
            return fam.payload_dense(payload).astype(dtype)

        out = dict(cm.params)
        for name, payload in cm.layers.items():
            if isinstance(payload, ConvPayload):  # scatter back to 4-d
                out[name + "_w"] = conv_weight_unmatrix(
                    _payload_dense(payload.payload), payload.kernel)
            else:
                out[name + "_w"] = _payload_dense(payload)
        return out
    shape_of = {r.name: r.shape for r in cm.report}
    out = _copy_spine(cm.params)
    for root in _BLOCK_ROOTS + ("shared_attn",):
        if isinstance(out.get(root), dict):
            for path, parent, k in _iter_linears(out[root], root):
                pat = cm.patterns.get(shape_of.get(path))
                parent[k] = _decompress_leaf(parent[k], pat, dtype,
                                             shape=shape_of.get(path))
    if isinstance(out.get("head"), dict):
        pat = cm.patterns.get(shape_of.get("head"))
        out["head"] = _decompress_leaf(out["head"], pat, dtype,
                                       shape=shape_of.get("head"))
    return out


# -------------------------------------------------------------- LeNet pass


def compile_lenet(
    params: Dict[str, jnp.ndarray],
    masks: Optional[Dict[str, np.ndarray]] = None,
    *,
    rules: CompileRules = CompileRules(block=(8, 4), min_weight_elems=512),
    blocks: Optional[Dict[str, Tuple[int, int]]] = None,
) -> CompressedModel:
    """Compress the whole LeNet-5 — convs AND FC layers (Table-1 workload).

    Every layer runs through the same analyze→decide→pack pipeline; convs
    are lowered onto their im2col matrix (``conv_weight_matrix``) so the
    identical CompressedLinear / QuantizedTensor / masked-dense payload
    families apply.  Returns a CompressedModel whose ``layers`` dict plugs
    straight into ``lenet_forward(params, x, compressed=cm.layers)``:

    * linear — CompressedLinear (sparse), QuantizedTensor (quant), masked
      dense array (dense-with-mask), absent (unmasked dense);
    * conv   — the same payload wrapped in a
      :class:`repro.core.dispatch.ConvPayload` (payload + static conv
      geometry), executed via ``conv_dispatch``; an unmasked dense conv
      stays a plain ``lax.conv`` passthrough (absent from ``layers``).

    Conv masks are accepted kernel-shaped ``(kh, kw, cin, cout)`` or
    im2col-shaped ``(K, N)``; a key matching no LeNet layer at all raises
    loudly (a typo would silently drop pruning).  ``patterns`` is keyed by
    the im2col (K, N) — distinct for every LeNet layer.
    """
    from ..models.lenet import CONV_OUT_HW, LAYERS, lenet_layer_specs

    names = [n for n, _, _ in LAYERS]
    for label, d in (("masks", masks), ("policies", rules.policies),
                     ("blocks", blocks)):
        unknown = set(d or {}) - set(names)
        if unknown:
            raise ValueError(
                f"{label} keys matched no LeNet layer: {sorted(unknown)} — "
                f"compile_lenet lowers every layer of {names} (convs "
                "included, via the im2col datapath); a typo here would "
                "silently drop the override")

    specs = {s.name: s for s in lenet_layer_specs(batch=rules.batch_tokens)}
    patterns: Dict[Tuple[int, int], BlockSparsePattern] = {}
    report: List[LayerReport] = []
    layers: Dict[str, Any] = {}
    for name, kind, shape in LAYERS:
        if kind == "conv":
            kh, kw, cin, cout = shape
            K, N = kh * kw * cin, cout
            w = conv_weight_matrix(np.asarray(params[name + "_w"],
                                              np.float32))
            spec = specs[name]
            m_scale = int(np.prod(CONV_OUT_HW[name]))
        else:
            K, N = shape
            w = np.asarray(params[name + "_w"], np.float32)
            spec = None  # linear leaves keep the default decode-shaped spec
            m_scale = 1
        block = _fit_block(K, N, (blocks or {}).get(name, rules.block))
        mask = np.asarray(masks[name], bool) if masks and name in masks else None
        if mask is not None:
            if kind == "conv" and mask.ndim == 4:
                if mask.shape != shape:
                    raise ValueError(
                        f"{name}: conv mask shape {mask.shape} does not "
                        f"match the kernel {shape}")
                mask = conv_weight_matrix(mask)
            if mask.shape != (K, N):
                raise ValueError(
                    f"{name}: mask shape {mask.shape} does not match the "
                    f"layer — expected {(K, N)}"
                    + (f" (im2col) or kernel-shaped {shape}"
                       if kind == "conv" else ""))
        if mask is not None and block is not None:
            bitmap = _mask_bitmap(mask, block)
            bd, ed = bitmap.sum() / bitmap.size, mask.sum() / mask.size
        else:
            bd = rules.block_density
            ed = rules.block_density * rules.in_block_density
        policy, bits = _decide_policy(name, (rules.policies or {}).get(name),
                                      K, N, rules, block=block,
                                      block_density=bd, element_density=ed,
                                      spec=spec)
        dense_bytes = K * N * 4
        # as in compile_model: a user mask is honoured under every policy
        if not payload_registry.policy_eliminates_blocks(policy):
            bd = 1.0
            ed = 1.0 if mask is None else mask.sum() / mask.size
        payload = None
        if policy == "dense":
            if mask is not None:  # masked dense payload (plain array)
                payload = jnp.asarray(w * mask, jnp.float32)
            comp_bytes = cont_bytes = dense_bytes
        else:
            pc = payload_registry.policy_compiler(policy)
            if payload_registry.policy_eliminates_blocks(policy) \
                    and mask is None:
                bitmap = _shared_bitmap(w[None], block, rules.block_density)
                mask = _element_mask(w, bitmap, block,
                                     rules.in_block_density)
            payload, pat, comp_bytes, cont_bytes, bd_r, ed_r = \
                pc.compile_payload(w, mask, bits=bits, rules=rules,
                                   block=block)
            if pat is not None:
                patterns[(K, N)] = pat
            if bd_r is not None:
                bd = bd_r
            if ed_r is not None:
                ed = ed_r
        if payload is not None:
            layers[name] = (ConvPayload(payload=payload, kernel=shape)
                            if kind == "conv" else payload)
        report.append(LayerReport(
            name=name, policy=policy, shape=(K, N), n_layers=1,
            dense_bytes=dense_bytes, compressed_bytes=int(comp_bytes),
            block_density=float(bd), element_density=float(ed),
            kind=kind, m_scale=m_scale, container_bytes=int(cont_bytes)))
    from ..models.lenet import lenet_fusion_plan

    return CompressedModel(params=params, patterns=patterns, report=report,
                           layers=layers, fusion=lenet_fusion_plan(layers))


def compile_conv(
    w4: np.ndarray,
    *,
    strides: Tuple[int, int] = (1, 1),
    padding: str = "VALID",
    dilation: Tuple[int, int] = (1, 1),
    mask: Optional[np.ndarray] = None,
    rules: CompileRules = CompileRules(block=(8, 4), min_weight_elems=512),
    policy: Optional[str] = None,
    name: str = "conv",
    in_hw: Optional[Tuple[int, int]] = None,
) -> Tuple["ConvPayload", Optional[BlockSparsePattern], LayerReport]:
    """Compile ONE conv kernel ``(kh, kw, cin, cout)`` to a ConvPayload.

    The standalone conv entry point for resnet-style geometry: unlike
    :func:`compile_lenet` (stride-1 VALID only) this carries arbitrary
    static ``strides``/``padding``/``dilation`` into the payload, so
    ``conv_dispatch`` fuses the full geometry.  The weight is lowered onto
    its im2col matrix (:func:`conv_weight_matrix`) and packed by whatever
    registered policy family ``policy`` names (``None`` = the same
    analyze→decide pipeline as the model passes).

    ``mask`` is accepted kernel-shaped ``(kh, kw, cin, cout)`` or
    im2col-shaped ``(K, N)``.  ``in_hw`` (input spatial size) sets the
    report's ``m_scale`` via :func:`repro.core.dispatch.conv_out_hw`;
    without it the report scores the conv as a single-token matmul.

    Returns ``(conv_payload, pattern_or_None, report_row)``.
    """
    w4 = np.asarray(w4, np.float32)
    if w4.ndim != 4:
        raise ValueError(
            f"{name}: expected a 4-d conv kernel (kh, kw, cin, cout), got "
            f"shape {w4.shape}")
    kernel = tuple(int(d) for d in w4.shape)
    kh, kw, cin, cout = kernel
    K, N = kh * kw * cin, cout
    w = conv_weight_matrix(w4)
    if mask is not None:
        mask = np.asarray(mask, bool)
        if mask.ndim == 4:
            if mask.shape != kernel:
                raise ValueError(
                    f"{name}: conv mask shape {mask.shape} does not match "
                    f"the kernel {kernel}")
            mask = conv_weight_matrix(mask)
        if mask.shape != (K, N):
            raise ValueError(
                f"{name}: mask shape {mask.shape} does not match the layer "
                f"— expected {(K, N)} (im2col) or kernel-shaped {kernel}")
    block = _fit_block(K, N, rules.block)
    if mask is not None and block is not None:
        bitmap = _mask_bitmap(mask, block)
        bd, ed = bitmap.sum() / bitmap.size, mask.sum() / mask.size
    else:
        bd = rules.block_density
        ed = rules.block_density * rules.in_block_density
    policy, bits = _decide_policy(name, policy, K, N, rules, block=block,
                                  block_density=bd, element_density=ed)
    dense_bytes = K * N * 4
    if not payload_registry.policy_eliminates_blocks(policy):
        bd = 1.0
        ed = 1.0 if mask is None else mask.sum() / mask.size
    pattern = None
    if policy == "dense":
        payload = jnp.asarray(w if mask is None else w * mask, jnp.float32)
        comp_bytes = cont_bytes = dense_bytes
    else:
        pc = payload_registry.policy_compiler(policy)
        if payload_registry.policy_eliminates_blocks(policy) and mask is None:
            bitmap = _shared_bitmap(w[None], block, rules.block_density)
            mask = _element_mask(w, bitmap, block, rules.in_block_density)
        payload, pattern, comp_bytes, cont_bytes, bd_r, ed_r = \
            pc.compile_payload(w, mask, bits=bits, rules=rules, block=block)
        if bd_r is not None:
            bd = bd_r
        if ed_r is not None:
            ed = ed_r
    m_scale = 1
    if in_hw is not None:
        ho, wo = conv_out_hw(tuple(in_hw), (kh, kw), tuple(strides), padding,
                             tuple(dilation))
        m_scale = int(ho * wo)
    cp = ConvPayload(payload=payload, kernel=kernel,
                     strides=tuple(int(s) for s in strides), padding=padding,
                     dilation=tuple(int(d) for d in dilation))
    rep = LayerReport(
        name=name, policy=policy, shape=(K, N), n_layers=1,
        dense_bytes=dense_bytes, compressed_bytes=int(comp_bytes),
        block_density=float(bd), element_density=float(ed),
        kind="conv", m_scale=m_scale, container_bytes=int(cont_bytes))
    return cp, pattern, rep


def realised_densities(cm: CompressedModel) -> Dict[str, Tuple[float, float]]:
    """{layer name: (block_density, element_density)} realised by the
    compression pass — the DSE's LayerSpec path feeds these back (via
    :func:`repro.core.dse.apply_realised_densities`) so bottleneck
    elimination iterates against what the pass actually packed, conv
    leaves included, instead of the reference-pruning estimates."""
    return {r.name: (float(r.block_density), float(r.element_density))
            for r in cm.report}
