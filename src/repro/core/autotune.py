"""DSE-coupled autotuner — closes the paper's Fig. 1 loop at the dispatch seam.

The paper's workflow is an automated design-space exploration: estimate each
layer's latency/resource under candidate configurations, pick per-layer
configurations under a budget, then refine against the realised hardware.
Our TPU adaptation had the estimator (:mod:`repro.core.cost_model`) and the
search (:mod:`repro.core.dse`) but executed every layer with hard-coded
128-tiles.  This module closes the loop, mapping Fig. 1's steps onto the
dispatch seam:

  Fig. 1 step                         here
  ---------------------------------   ------------------------------------
  1. per-layer configuration space    :func:`sparse_candidates` /
     (folding / sparsity choices)     :func:`quant_candidates` — legal row
                                      tiles (sublane multiples), bn/bk in
                                      {128, 256, 512} where they divide,
                                      Pallas-vs-XLA backend choice
  2. latency/resource estimation      :func:`repro.core.cost_model.tile_roofline`
                                      seeds the search order; infeasible
                                      tiles (VMEM) are pruned up front
  3. iterative refinement against     :func:`autotune_leaf` measures the
     the realised design              top candidates (compiled timings on
                                      TPU; the compiled XLA twin on CPU —
                                      interpret-mode kernels are never
                                      timed, their ranking stays roofline)
  4. emit the chosen configuration    :class:`TunedTable`, cached on disk
                                      keyed by (shape, dtype, backend,
                                      pattern-schedule hash) and threaded
                                      through ``DispatchConfig.tuned`` so
                                      every serving surface consumes tuned
                                      tiles at trace time — zero per-call
                                      overhead

The per-layer *bit-width* axis ({None, 8, 4}) is compile-time, not
dispatch-time: :func:`tuned_policy` re-ranks it with
``cost_model.network_estimate`` and is consulted by ``compile_sparse``
behind ``policy="autotune"``.  :func:`dse_retune` is the matching hook for
``dse.run_dse`` — step 3's bottleneck elimination can propose a retune of
the bottleneck layer's folding config as one of its moves.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.sparse_matmul.kernel import _row_tile, _sublane
from . import payload_registry
from .cost_model import (
    HWSpec,
    LayerSpec,
    device_hw,
    decode_linear_spec,
    layer_latency,
    network_estimate,
    tile_roofline,
    tile_vmem_bytes,
)
from .folding import FoldingConfig
from .sparsity import BlockSparsePattern

__all__ = [
    "AUTOTUNE_CACHE_ENV",
    "TunedConfig",
    "TunedTable",
    "TuneOptions",
    "bucket_m",
    "default_cache_path",
    "load_table",
    "schedule_hash",
    "tune_key",
    "sparse_candidates",
    "quant_candidates",
    "autotune_attn",
    "autotune_leaf",
    "autotune_model",
    "autotune_lenet",
    "tuned_policy",
    "dse_retune",
]

AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_CACHE = os.path.join("results", "autotune_cache.json")
_QUANT_TILES = (128, 256, 512)  # bn / bk choices where they divide
_CACHE_VERSION = 1


def default_cache_path() -> str:
    return os.environ.get(AUTOTUNE_CACHE_ENV, _DEFAULT_CACHE)


# ------------------------------------------------------------- tuned config


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One leaf's chosen execution configuration (all trace-time statics).

    ``use_pallas=False`` means the XLA twin (no tile knobs).  ``bm=None``
    on the Pallas path means the auto row tile (decode entry for thin M).
    ``bn``/``bk`` apply to the dense/quant kernel only — the sparse
    kernel's weight tiles are fixed by the compiled pattern.
    """

    use_pallas: bool
    bm: Optional[int] = None
    bn: Optional[int] = None
    bk: Optional[int] = None
    measured_us: Optional[float] = None   # timing of the winner (None = unmeasured)
    predicted_us: Optional[float] = None  # roofline seed score

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "TunedConfig":
        fields = {f.name for f in dataclasses.fields(TunedConfig)}
        kw = {k: v for k, v in dict(d).items() if k in fields}
        if not isinstance(kw.get("use_pallas"), bool):
            raise ValueError(f"bad TunedConfig entry: {d!r}")
        # range-validate the tiles too: a value-corrupted (but JSON-valid)
        # cache must mean "retune", never a crash inside a forward pass
        for k, legal in (("bm", range(8, 129, 8)),
                         ("bn", _QUANT_TILES), ("bk", _QUANT_TILES)):
            if kw.get(k) is not None:
                kw[k] = int(kw[k])
                if kw[k] not in legal:
                    raise ValueError(f"illegal {k}={kw[k]} in entry: {d!r}")
        return TunedConfig(**kw)


class TunedTable:
    """Key -> TunedConfig map with an on-disk JSON form.

    Deliberately a plain class (identity hash/eq): it rides inside the
    frozen :class:`repro.core.dispatch.DispatchConfig`, which must stay
    hashable.  ``load`` never raises on a missing or corrupted cache file —
    a bad cache means "retune", not "crash".  ``log`` records what the last
    tuning run did per key (cache hit vs how many candidates were timed);
    it is never serialised.
    """

    def __init__(self, entries: Optional[Dict[str, TunedConfig]] = None,
                 path: Optional[str] = None):
        self.entries: Dict[str, TunedConfig] = dict(entries or {})
        self.path = path
        self.log: List[Dict[str, Any]] = []

    def get(self, key: str) -> Optional[TunedConfig]:
        return self.entries.get(key)

    def put(self, key: str, cfg: TunedConfig) -> None:
        self.entries[key] = cfg

    def __len__(self) -> int:
        return len(self.entries)

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    def n_timings(self) -> int:
        """Candidates actually timed by the last tuning run (0 = pure cache)."""
        return sum(e.get("n_timed", 0) for e in self.log)

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path or default_cache_path()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        blob = {
            "version": _CACHE_VERSION,
            "entries": {k: v.to_json() for k, v in sorted(self.entries.items())},
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
        os.replace(tmp, path)  # atomic: a crashed save never corrupts
        self.path = path
        return path

    @classmethod
    def load(cls, path: str) -> "TunedTable":
        table = cls(path=path)
        try:
            with open(path) as f:
                blob = json.load(f)
            if blob.get("version") != _CACHE_VERSION:
                return table
            for k, v in blob.get("entries", {}).items():
                table.entries[str(k)] = TunedConfig.from_json(v)
        except (OSError, ValueError, TypeError, AttributeError):
            # missing / truncated / garbage cache: start empty and retune
            table.entries.clear()
        return table


_LOAD_MEMO: Dict[Tuple[str, float, int], TunedTable] = {}


def load_table(path: Optional[str] = None) -> TunedTable:
    """Load (memoised on mtime+size) — the trace-time entry ``resolve``
    uses for ``dispatch="autotune"``; a missing cache is an empty table."""
    path = path or default_cache_path()
    try:
        st = os.stat(path)
        key = (os.path.abspath(path), st.st_mtime, st.st_size)
    except OSError:
        return TunedTable(path=path)
    hit = _LOAD_MEMO.get(key)
    if hit is None:
        hit = TunedTable.load(path)
        _LOAD_MEMO.clear()  # one live file version is enough
        _LOAD_MEMO[key] = hit
    return hit


# --------------------------------------------------------------------- keys


def schedule_hash(pattern: BlockSparsePattern) -> str:
    """Deterministic digest of the static schedule (shape, block, bitmap)."""
    h = hashlib.sha1()
    h.update(repr((tuple(pattern.shape), tuple(pattern.block))).encode())
    h.update(np.packbits(np.asarray(pattern.bitmap, bool)).tobytes())
    return h.hexdigest()[:16]


def bucket_m(M: int) -> int:
    """M-bucket for tuned keys: next power of two, capped at 8192.

    Decode row counts (M = ``batch_slots``: 1, 2, 4, 8 …) are already
    powers of two, so thin decode tiles keep exact buckets; prefill GEMMs
    (M = B*T: hundreds to tens of thousands of rows) collapse into coarse
    buckets where the tile choice is M-insensitive anyway.  One tuned
    entry per bucket means a decode-tuned table never serves (or is
    shadowed by) a prefill entry for a nearby-but-different M — the
    prefill/decode split falls out of the call sites: every dispatch
    looks up its *own* trace-time M, and same-bucket shapes share.
    """
    M = max(1, int(M))
    b = 1
    while b < M and b < 8192:
        b *= 2
    return b


def tune_key(*, kind: str, M: int, K: int, N: int, dtype,
             backend: Optional[str] = None,
             pattern: Optional[BlockSparsePattern] = None,
             container: Optional[str] = None,
             leaf: Optional[str] = None) -> str:
    """Cache key: (kind, shape, dtype, backend, pattern-schedule hash).

    ``M`` is part of the shape — tile choice at decode M=4 and prefill
    M=2048 are different problems — but enters through :func:`bucket_m`,
    so a decode call site (M = engine ``batch_slots``) and a prefill call
    site (M = B*T) of the same leaf resolve to different entries while
    nearby large-M shapes share one.  ``backend`` defaults to the current
    ``jax.default_backend()``: CPU timings must never serve TPU lookups.
    ``kind`` carries the op family too: an im2col'd conv tunes under
    ``conv_sparse`` / ``conv_quant``, so it never collides with a linear
    leaf at the same (M, K, N).  ``container`` names a non-default storage
    container — bit-packed int4 leaves tag ``int4x2``
    (:data:`repro.core.quant.PACKED_CONTAINER`) so their tuned entries
    never cross the int8-container entries: on hardware the two stream
    different HBM bytes, so a tile choice tuned for one is not evidence
    for the other.  ``leaf`` appends a per-leaf suffix — the override
    path for two leaves that share the whole base key (same shape, dtype,
    backend AND schedule) but should be tuned apart; the dispatch lookup
    consults the per-leaf key first, then the shared one.
    """
    backend = backend or jax.default_backend()
    sched = schedule_hash(pattern) if pattern is not None else "dense"
    base = (f"{kind}:M{bucket_m(M)}:K{int(K)}:N{int(N)}:"
            f"{jnp.dtype(dtype).name}:{backend}:{sched}")
    if container is not None:
        base = f"{base}:container={container}"
    return base if leaf is None else f"{base}:leaf={leaf}"


# --------------------------------------------------------------- candidates


def _bm_candidates(dtype) -> List[int]:
    """Legal sparse row tiles: power-of-two sublane multiples up to 128."""
    sub = _sublane(jnp.dtype(dtype))
    out, b = [], sub
    while b <= 128:
        out.append(b)
        b *= 2
    return out


def sparse_candidates(M: int, pattern: BlockSparsePattern,
                      x_dtype) -> List[TunedConfig]:
    """XLA twin + every legal Pallas row tile (None = auto/decode entry)."""
    cands = [TunedConfig(use_pallas=False), TunedConfig(use_pallas=True, bm=None)]
    for bm in _bm_candidates(x_dtype):
        cands.append(TunedConfig(use_pallas=True, bm=bm))
    return cands


def quant_candidates(M: int, K: int, N: int, x_dtype,
                     hw: Optional[HWSpec] = None) -> List[TunedConfig]:
    """XLA twin + (bm, bn, bk) grid over dividing 128-multiples, gated on
    the scoped VMEM limit of ``hw`` (default: this device's spec)."""
    hw = hw or device_hw()
    cands = [TunedConfig(use_pallas=False), TunedConfig(use_pallas=True)]
    x_bytes = jnp.dtype(x_dtype).itemsize
    for bm in _bm_candidates(x_dtype):
        for bn in _QUANT_TILES:
            if N % bn:
                continue
            for bk in _QUANT_TILES:
                if K % bk:
                    continue
                if tile_vmem_bytes(bm, bk, bn, x_bytes=x_bytes,
                                   w_bytes=1) > hw.vmem_scoped_bytes:
                    continue
                cands.append(TunedConfig(use_pallas=True, bm=bm, bn=bn, bk=bk))
    return cands


def _predict_us(kind: str, cand: TunedConfig, *, M: int, K: int, N: int,
                pattern: Optional[BlockSparsePattern], weight_bits: int,
                x_dtype, hw: HWSpec) -> float:
    if payload_registry.kind_needs_pattern(kind):
        assert pattern is not None
        bk, bn = pattern.block
        n_blocks = pattern.n_blocks_present
    else:
        from .dispatch import quant_tiles
        bk0, bn0 = quant_tiles(K, N)
        bk, bn = cand.bk or bk0, cand.bn or bn0
        n_blocks = None
    if cand.use_pallas:
        # None = the decode entry's auto row tile — the kernel's own rule
        bm = cand.bm if cand.bm is not None else _row_tile(M, jnp.dtype(x_dtype))
        s = tile_roofline(M=M, K=K, N=N, bm=bm, bk=bk, bn=bn,
                          n_blocks=n_blocks, weight_bits=weight_bits, hw=hw)
    else:
        # XLA twin: same roofline terms at the full-problem granularity —
        # one "launch", no per-step schedule overhead modelled
        s = tile_roofline(M=M, K=K, N=N, bm=min(128, max(8, M)), bk=bk,
                          bn=bn, n_blocks=n_blocks, weight_bits=weight_bits,
                          hw=hw, launch=False)
    return s * 1e6


# -------------------------------------------------------------- measurement


def _time_fn(fn: Callable[[], Any], iters: int, warmup: int = 2) -> float:
    """Mean wall time in microseconds of a jitted thunk (compile excluded)."""
    r = None
    for _ in range(max(1, warmup)):
        r = fn()
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e6


@dataclasses.dataclass(frozen=True)
class TuneOptions:
    """Search-effort knobs.

    ``max_measured`` bounds the number of candidates actually timed per
    leaf (the roofline ordering decides which; the XLA twin and the
    default-tile Pallas candidate are always in the measured set, so the
    tuned pick can never lose to the default it was seeded from).
    ``measure_interpret=True`` times interpret-mode kernels off-TPU —
    meaningless for production (interpret is Python-speed) but it exercises
    the full measurement loop in tests.
    """

    max_measured: int = 6
    iters: int = 10
    warmup: int = 2
    measure_interpret: bool = False
    hw: Optional[HWSpec] = None  # None = this device's spec (device_hw)


def _runner(kind: str, cand: TunedConfig, x: jnp.ndarray,
            leaf: Dict[str, jnp.ndarray],
            pattern: Optional[BlockSparsePattern],
            interpret: bool) -> Callable[[], Any]:
    """Build a jitted thunk executing ``cand`` on real arrays.

    Delegates to the registered ``tune_runner`` of the kind's unpacked
    reference family — the one place that knows how to rebuild its
    payload from reference leaves and call its kernel/twin entry."""
    fam = payload_registry.kind_family(kind)
    if fam is None or fam.tune_runner is None:
        raise ValueError(
            f"unknown tune kind {kind!r} — tunable kinds: "
            f"{payload_registry.tunable_kinds()}")
    return fam.tune_runner(cand, x, leaf, pattern, interpret)


def autotune_leaf(
    kind: str,
    x: jnp.ndarray,
    leaf: Dict[str, jnp.ndarray],
    *,
    pattern: Optional[BlockSparsePattern] = None,
    weight_bits: int = 8,
    options: TuneOptions = TuneOptions(),
    table: Optional[TunedTable] = None,
    key: Optional[str] = None,
    container: Optional[str] = None,
) -> TunedConfig:
    """Tune one compiled leaf: roofline-seeded search, measured refinement.

    ``kind`` is "sparse" (needs ``pattern``) or "quant", optionally
    prefixed ``conv_`` for an im2col'd conv leaf — the search space and
    runner are those of the underlying matmul (a conv IS that matmul at
    M = B*H_out*W_out), only the cache key differs.  A pre-existing
    ``table`` entry for ``key`` short-circuits everything (zero timings —
    the on-disk cache contract).  Off-TPU, interpret-mode Pallas timings
    are never trusted: Pallas candidates keep their roofline score and the
    measured XLA twin wins unless ``options.measure_interpret`` is set.

    Bit-packed container leaves tune under a ``container``-tagged key
    (never shared with the unpacked-container entries); their family's
    ``tune_prepare`` hook unpacks the codes into the reference form the
    measurement runner times — off-TPU that is the only honest signal
    anyway (interpret timings are untrusted and the XLA twin unpacks at
    trace time), and on TPU the roofline seed already accounts the packed
    weight traffic.
    """
    family = kind
    for prefix in ("fusedconv_", "conv_"):
        if kind.startswith(prefix):
            family = kind[len(prefix):]
            break
    fam = payload_registry.kind_family(family)
    if fam is None:
        raise ValueError(
            f"unknown tune kind {kind!r} — tunable kinds: "
            f"{payload_registry.tunable_kinds()}")
    M, K_x = int(np.prod(x.shape[:-1], dtype=int)), x.shape[-1]
    lf = payload_registry.family_for_leaves(leaf)
    if lf is not None and lf.tune_prepare is not None:
        # packed container -> reference codes for the runner + key tag
        leaf, cont = lf.tune_prepare(leaf, pattern, K_x)
        container = container or cont
    K, N = fam.leaf_kn(leaf, pattern)
    assert K_x == K, (K_x, K)
    if key is None:
        key = tune_key(kind=kind, M=M, K=K, N=N, dtype=x.dtype,
                       pattern=pattern, container=container)
    if table is not None:
        hit = table.get(key)
        if hit is not None:
            table.log.append({"key": key, "cached": True, "n_timed": 0})
            return hit

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    measurable_pallas = on_tpu or options.measure_interpret

    hw = options.hw or device_hw()
    if fam.needs_pattern:
        cands = sparse_candidates(M, pattern, x.dtype)
    else:
        cands = quant_candidates(M, K, N, x.dtype, hw)
    scored = [(c, _predict_us(family, c, M=M, K=K, N=N, pattern=pattern,
                              weight_bits=weight_bits, x_dtype=x.dtype,
                              hw=hw)) for c in cands]
    scored.sort(key=lambda cp: cp[1])

    # measured set: the XLA twin + the default-tile Pallas candidate are
    # always timed (when timeable); the rest by roofline order.
    def _is_default(c: TunedConfig) -> bool:
        return c.use_pallas and c.bm is None and c.bn is None and c.bk is None

    measured: List[Tuple[TunedConfig, float, float]] = []  # (cand, us, pred)
    n_timed = 0
    for cand, pred in scored:
        if cand.use_pallas and not measurable_pallas:
            continue
        forced = (not cand.use_pallas) or _is_default(cand)
        if not forced and n_timed >= options.max_measured:
            continue
        us = _time_fn(_runner(family, cand, x, leaf, pattern, interpret),
                      options.iters, options.warmup)
        measured.append((cand, us, pred))
        n_timed += 1

    if measured:
        # Measured refinement only ranks candidates compiled for the active
        # backend: off-TPU a Pallas candidate runs in interpret mode, and an
        # interpret timing must never beat the compiled XLA twin on wall
        # clock (interpret overhead is not the TPU cost it stands in for).
        # measure_interpret surfaces interpret timings in the log, but the
        # winner is still picked among backend-valid candidates.
        valid = [t for t in measured if on_tpu or not t[0].use_pallas]
        cand, us, pred = min(valid or measured, key=lambda t: t[1])
        winner = dataclasses.replace(cand, measured_us=float(us),
                                     predicted_us=float(pred))
    else:  # nothing timeable (can't happen in practice: XLA always is)
        cand, pred = scored[0]
        winner = dataclasses.replace(cand, predicted_us=float(pred))
    if table is not None:
        table.put(key, winner)
        table.log.append({"key": key, "cached": False, "n_timed": n_timed})
    return winner


# ------------------------------------------------- packed-attention tuning

# kv-tile candidates for the fused packed-attention read: power-of-two row
# counts (the compiled kernel takes 128 multiples, or any tile that covers
# the whole extent — see dispatch.attn_packed_eligible)
_ATTN_BT_CANDIDATES = (8, 16, 32, 64, 128)


def autotune_attn(
    *,
    B: int,
    T: int,
    H: int,
    Hkv: int,
    Dh: int,
    x_dtype=jnp.float32,
    options: TuneOptions = TuneOptions(),
    table: Optional[TunedTable] = None,
    key: Optional[str] = None,
    save: bool = True,
    seed: int = 0,
) -> TunedConfig:
    """Tune the fused packed-KV attention read (kind ``attn_packed``).

    The search space is one axis — the kv tile rows ``bt`` (carried in the
    entry's ``bm`` slot) — crossed with kernel-vs-twin.  Candidates run on
    synthetic packed codes + scales at the serving shape (B slots, T cache
    positions, full-length reads: the steady-state worst case).  Off-TPU
    the kernel runs in interpret mode and is never timed (unless
    ``options.measure_interpret``), so the winner is the honestly-measured
    jnp twin at its best tile — still a real signal, since the twin IS the
    CPU serving path.  A pre-existing ``table`` entry for ``key``
    short-circuits with zero timings, sharing the on-disk cache contract
    of :func:`autotune_leaf`.

    The attention read has no payload family (KV caches are activations,
    not compiled weight leaves), so this tunes against the kernel/twin
    entries directly instead of going through ``autotune_leaf``'s
    registry runners.
    """
    from ..kernels.flash_attention.decode_packed import (
        packed_decode_attention,
        tiled_packed_attention,
    )
    from .quant import pack_int4

    if key is None:
        key = tune_key(kind="attn_packed", M=B, K=T, N=H * Dh, dtype=x_dtype)
    if table is not None:
        hit = table.get(key)
        if hit is not None:
            table.log.append({"key": key, "cached": True, "n_timed": 0})
            return hit

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    measurable_pallas = on_tpu or options.measure_interpret

    rng = np.random.default_rng(seed)
    codes_k = rng.integers(-7, 8, size=(B, T, Hkv, Dh)).astype(np.int8)
    codes_v = rng.integers(-7, 8, size=(B, T, Hkv, Dh)).astype(np.int8)
    k_p = pack_int4(jnp.asarray(codes_k), axis=-1)
    v_p = pack_int4(jnp.asarray(codes_v), axis=-1)
    k_s = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, Hkv)), jnp.float32)
    v_s = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, Hkv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), x_dtype)
    lengths = jnp.full((B, 1), T, jnp.int32)

    # the engine pins ONE bt for its lifetime but reads the cache at
    # bucketed power-of-two extents (32, 64, ... T) as slots fill, so a
    # candidate's cost is the SUM over those extents — timing only the
    # full-length read crowns the tile that amortises best at T (one big
    # tile) and ignores that it pads every short extent back up to T,
    # which is where a serving engine spends most of its steps
    extents = []
    e = 32
    while e < T:
        extents.append(e)
        e *= 2
    extents.append(T)

    measured: List[Tuple[TunedConfig, float]] = []
    n_timed = 0
    for bt in _ATTN_BT_CANDIDATES:
        if bt > T and bt != _ATTN_BT_CANDIDATES[0]:
            continue  # one tile already covers the whole cache

        def twin(bt=bt):
            return [tiled_packed_attention(
                q, k_p[:, :e], v_p[:, :e], k_s[:, :e], v_s[:, :e],
                jnp.minimum(lengths, e), bt=bt, packed=True)
                for e in extents]

        us = _time_fn(twin, options.iters, options.warmup)
        measured.append((TunedConfig(use_pallas=False, bm=bt), us))
        n_timed += 1
        from .dispatch import attn_packed_eligible
        if measurable_pallas and all(attn_packed_eligible(Dh, bt, e)
                                     for e in extents):

            def kern(bt=bt):
                return [packed_decode_attention(
                    q, k_p[:, :e], v_p[:, :e], k_s[:, :e], v_s[:, :e],
                    jnp.minimum(lengths[:, 0], e), bt=bt,
                    interpret=interpret)
                    for e in extents]

            us = _time_fn(kern, options.iters, options.warmup)
            measured.append((TunedConfig(use_pallas=True, bm=bt), us))
            n_timed += 1

    valid = [t for t in measured if on_tpu or not t[0].use_pallas]
    cand, us = min(valid or measured, key=lambda t: t[1])
    winner = dataclasses.replace(cand, measured_us=float(us))
    if table is not None:
        table.put(key, winner)
        table.log.append({"key": key, "cached": False, "n_timed": n_timed})
        if save and table.path:
            table.save()
    return winner


# ---------------------------------------------------------- whole-model API


def _leaf_by_path(tree: Any, path: str) -> Dict[str, Any]:
    node = tree
    for k in path.split("/"):
        node = node[k]
    return node


def _representative(leaf: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """First layer of a stacked leaf — same shape/pattern for the stack.

    Stacked-ness comes from the registry's per-leaf ``leaf_ndim``
    declarations, so a new family's stacked leaves slice correctly
    without this module learning its names."""
    return payload_registry.representative_leaves(leaf)


def autotune_model(
    cm,
    *,
    M,
    x_dtype=jnp.float32,
    options: TuneOptions = TuneOptions(),
    path: Optional[str] = None,
    save: bool = True,
    seed: int = 0,
    per_leaf: bool = False,
) -> TunedTable:
    """Tune every compiled (sparse / quant) leaf of a CompressedModel at
    batch-rows ``M`` (decode: the engine's slot count; prefill: B*T).

    ``M`` may also be a sequence of row counts — e.g. ``(batch_slots,
    batch * prompt_len)`` tunes the thin decode row tiles and the prefill
    GEMMs in one pass, each under its own :func:`bucket_m` key, so a
    serving engine and its prefill path consume the same table with
    per-call-site entries.

    Loads the on-disk table first — already-tuned keys are never re-timed
    (``table.n_timings() == 0`` on a warm cache) — and saves the merged
    table back.  One key serves every same-shape leaf: the schedule hash
    is shared by construction (one pattern per (K, N) shape).  Conv
    leaves tune as their im2col matmul — ``conv_sparse`` / ``conv_quant``
    kinds at ``M * H_out*W_out`` rows (``LayerReport.m_scale``) — so their
    entries never collide with linears at the same shape.

    ``per_leaf=True`` writes every entry under its per-leaf key
    (``...:leaf=<name>``) instead of the shared shape key: the override
    path for models whose same-shape leaves should be tuned apart.  The
    dispatch lookup prefers a per-leaf entry when the caller names its
    leaf, falling back to the shared one.
    """
    path = path or default_cache_path()
    table = TunedTable.load(path)
    table.log = []
    rng = np.random.default_rng(seed)
    Ms = (M,) if isinstance(M, (int, np.integer)) else tuple(M)
    done = set()
    tunable = payload_registry.tunable_kinds()
    for r in cm.report:
        if r.policy not in tunable:
            continue
        K, N = r.shape
        kind = ("conv_" if r.kind == "conv" else "") + r.policy
        pattern = cm.patterns.get((K, N)) \
            if payload_registry.kind_needs_pattern(r.policy) else None
        if cm.layers:  # LeNet-style payloads
            leaf = _payload_leaf(cm.layers.get(r.name))
            if leaf is None:
                continue
        else:
            leaf = _representative(_leaf_by_path(cm.params, r.name))
        lf = payload_registry.family_for_leaves(leaf)
        container = lf.container if lf is not None else None
        for M_rows in Ms:
            M_leaf = int(M_rows) * max(1, int(r.m_scale))
            key = tune_key(kind=kind, M=M_leaf, K=K, N=N, dtype=x_dtype,
                           pattern=pattern, container=container,
                           leaf=r.name if per_leaf else None)
            if key in done:
                continue
            done.add(key)
            x = jnp.asarray(rng.normal(size=(M_leaf, K)), x_dtype)
            if container is not None:
                # bit-packed containers: code width from the tag
                from .quant import PACKED_CONTAINER, PACKED_CONTAINER_INT2
                wbits = {PACKED_CONTAINER: 4,
                         PACKED_CONTAINER_INT2: 2}.get(container, 4)
            else:
                w_arr = leaf.get(lf.code_leaf) if lf is not None else None
                wbits = 8 if w_arr is not None and \
                    w_arr.dtype == jnp.int8 else 32
            autotune_leaf(kind, x, leaf, pattern=pattern, weight_bits=wbits,
                          options=options, table=table, key=key,
                          container=container)
    if save:
        table.save(path)
    return table


def _payload_leaf(payload) -> Optional[Dict[str, jnp.ndarray]]:
    """Leaf-dict view of a compile_sparse payload for the tuner.

    Resolves through :func:`payload_registry.unwrap_payload` — the SAME
    helper the dispatch path uses — so the container-vs-unpacked key
    decision (which axis a bit-packed payload is packed along, whether it
    executes via in-kernel decode or trace-time unpack) can never drift
    between tuning and dispatch again."""
    from .dispatch import ConvPayload

    if isinstance(payload, ConvPayload):  # conv leaf: tune its im2col matmul
        payload = payload.payload
    fam, leaves, _ = payload_registry.unwrap_payload(payload)
    if fam is None or fam.kind is None:
        return None  # masked dense (or untunable family): nothing to tune
    return dict(leaves)


def autotune_lenet(cm, *, M: int, **kw) -> TunedTable:
    """Alias of :func:`autotune_model` for compile_lenet results (payload
    layers) — the report/pattern walk already handles both forms."""
    return autotune_model(cm, M=M, **kw)


# --------------------------------------- compile-time bit-width re-ranking


def tuned_policy(
    K: int,
    N: int,
    *,
    rules,
    block_density: float,
    element_density: float,
    sparse_eligible: bool,
    spec: Optional[LayerSpec] = None,
) -> Tuple[str, int]:
    """Per-layer (policy, quant_bits) pick behind ``policy="autotune"``.

    Re-ranks the candidate space {dense(16), quant(8), quant(4),
    sparse(8), sparse(4)} by ``cost_model.network_estimate`` over a
    decode-shaped one-layer network — the same estimator the DSE trusts,
    instead of compile_sparse's fixed three-way latency compare.  The
    storage floor still keeps tiny layers dense.  ``spec`` overrides the
    default linear-shaped LayerSpec (conv leaves pass their own: MACs
    scaled by output H·W, real activation traffic).
    """
    if K * N < rules.min_weight_elems:
        return "dense", 16
    if spec is None:
        spec = decode_linear_spec(K, N, rules.batch_tokens)
    hw = rules.hw or device_hw()
    cands: List[Tuple[str, int, FoldingConfig]] = [
        ("dense", 16, FoldingConfig(parallelism=hw.lanes, unroll="factor",
                                    quant_bits=16)),
        ("quant", 8, FoldingConfig(parallelism=hw.lanes, unroll="factor",
                                   quant_bits=8)),
        ("quant", 4, FoldingConfig(parallelism=hw.lanes, unroll="factor",
                                   quant_bits=4)),
    ]
    if sparse_eligible:
        for bits in (8, 4):
            cands.append(("sparse", bits, FoldingConfig(
                parallelism=hw.lanes, unroll="sparse",
                block_density=block_density,
                element_density=element_density, quant_bits=bits)))
    best = min(cands, key=lambda c: network_estimate([spec], [c[2]], hw).ii)
    return best[0], best[1]


# ------------------------------------------------------------ DSE coupling


def dse_retune(spec: LayerSpec, cfg: FoldingConfig,
               hw: Optional[HWSpec] = None) -> Optional[FoldingConfig]:
    """Bottleneck retune move for :func:`repro.core.dse.run_dse`.

    When step 3's bottleneck elimination stalls on a layer, this proposes
    re-ranking its quant bit-width ({16, 8, 4}) under the *current* unroll
    level by ``layer_latency`` — the cheapest move in the space (no
    refolding, no resource growth beyond storage).  Returns None when the
    current config is already the best, so the DSE's move loop stays
    monotone.
    """
    hw = hw or device_hw()
    best_lat, best = None, None
    for bits in (16, 8, 4):
        trial = cfg.replace(quant_bits=bits)
        lat = layer_latency(spec, trial, hw)["total"]
        if best_lat is None or lat < best_lat:
            best_lat, best = lat, trial
    if best is None or best == cfg:
        return None
    return best
