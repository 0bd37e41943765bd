"""Batched serving engine: continuous batching with chunked prefill.

Two execution modes, chosen per model family at construction:

* **Chunked interleave** (attention-only families: dense, vlm) — prompts
  run through :func:`repro.models.model.prefill_step` in fixed-size
  chunks (``prefill_chunk`` tokens, the engine's per-step token budget),
  quantise-packing each chunk's K/V vectorised and writing straight into
  the cache container; every engine step advances ONE prefilling slot by
  one chunk *and* every decoding slot by one token (``decode_step`` with
  an ``active`` mask), so a long prompt never stalls the decoding slots.
  The first generated token falls out of the final prefill chunk's
  logits — no extra decode step between prefill and generation, which is
  the TTFT win.  A prompt whose chunk schedule cannot fit the cache
  (``ceil(P/C)·C > max_len``) falls back to the legacy token drip for
  that request only.

* **Legacy drip** (moe / ssm / hybrid) — exactly one token per active
  slot per step through the jitted ``decode_step``, prompts fed one
  token at a time.  Recurrent state must advance token-by-token and a
  MoE router's static capacity depends on the token count, so these
  families keep the original path verbatim.

Prefill operates on a gathered batch-of-one view of the slot's cache
(``dynamic_slice_in_dim`` over the explicit batch-axis spec), so a chunk
write can never clobber a neighbouring slot; the decoding slots' masked
garbage rows land beyond their live length and are overwritten by their
next real write.  Cache reads are bucketed to a power-of-two extent
(``_bucket_t``) with the kv tile size pinned once at startup — the fused
read skips dead tiles, so every bucket walks the same live tiles and
bucketing changes compile shapes, not the tiles attended.

Per-phase accounting rides along: ``stats()`` reports prefill/decode
step counts, token counts and per-step wall-clock, ``tokens_processed()``
the total token throughput numerator, and each :class:`Request` carries
``t_submit`` / ``t_first`` / ``t_done`` stamps (TTFT = t_first −
t_submit).

This is the same ``decode_step`` the dry run lowers for the 256-chip
mesh.  ``chip_smoke.py`` serves a compressed llama3.2-1b through this
engine on one TPU chip; the tests and examples run it on the CPU (Pallas
kernels in interpret mode).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ArchConfig
from ..models.model import (cache_batch_axes, decode_step, init_cache,
                            prefill_step)

# families whose prompts run through the chunked prefill path
_CHUNKED_FAMILIES = ("dense", "vlm")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (T,) int32
    max_new_tokens: int = 16
    out: Optional[List[int]] = None  # generated tokens
    t_submit: Optional[float] = None  # perf_counter at submit()
    t_first: Optional[float] = None   # ... at first generated token (TTFT)
    t_done: Optional[float] = None    # ... at completion


class ServeEngine:
    """``params`` may be a raw parameter pytree or a
    :class:`repro.core.compile_sparse.CompressedModel` — the engine then
    serves straight from the compacted format (int8 / block-compacted
    leaves), with the static pattern table baked into the jitted step.

    ``dispatch`` picks the kernel path for the compiled leaves ("auto" |
    "pallas" | "jnp" | "autotune" | DispatchConfig | None =
    REPRO_FORCE_DISPATCH env); it is resolved once here and baked into the
    jitted ``decode_step`` alongside the pattern side-table, so every
    engine step runs the same engine-free datapath as ``forward``.

    ``autotune`` couples the engine to :mod:`repro.core.autotune`: ``True``
    tunes every compiled leaf at this engine's decode shape (M =
    ``batch_slots``) against the on-disk cache — a warm cache is a pure
    lookup, zero re-timing — and a :class:`TunedTable` instance is used
    as-is.  With a quantised KV cache the fused attention read is tuned
    too (:func:`repro.core.autotune.autotune_attn` — kind ``attn_packed``
    at M = ``batch_slots``), and the winning kv tile size is pinned for
    the engine's lifetime.  The tuned tiles are baked into the jitted
    step like everything else (identical numerics, trace-time choice).
    The engine pins the dispatch ``m_bucket`` to its decode rows so tuned
    lookups always hit the thin decode bucket, never a prefill entry.

    ``kv_cache`` picks the KV-cache container
    (:data:`repro.models.blocks.KV_CACHE_MODES`): ``"int4x2"`` stores the
    attention cache as bit-packed int4 codes + per-(slot, pos, head)
    scales — the decode step quantise-packs each appended row and the
    fused attention read nibble-decodes tiles in-register, so
    cache-resident bytes drop ~7x vs the f32 form with no engine-visible
    API change.  ``packed_read`` selects that read ("fused", default) or
    the pre-fused full-container decode ("unpack" — the bench baseline).

    ``prefill_chunk`` is the prompt-chunk size AND the per-step prefill
    token budget of the chunked interleave (attention-only families);
    other families ignore it."""

    def __init__(self, params, cfg: ArchConfig, *, batch_slots: int = 4,
                 max_len: int = 256, patterns=None, dispatch=None,
                 autotune=False, autotune_options=None,
                 kv_cache: str = "float", prefill_chunk: int = 16,
                 packed_read: str = "fused"):
        import dataclasses as _dc

        from ..core.compile_sparse import CompressedModel
        from ..core.dispatch import ATTN_BT_DEFAULT
        from ..core.dispatch import resolve as resolve_dispatch
        cm = params if isinstance(params, CompressedModel) else None
        if cm is not None:
            patterns = cm.patterns if patterns is None else patterns
            params = cm.params
        dispatch = resolve_dispatch(dispatch)
        table = None
        if autotune is not False and autotune is not None:
            from ..core.autotune import TunedTable, autotune_model
            if isinstance(autotune, TunedTable):
                table = autotune
            else:
                if cm is None:
                    raise ValueError(
                        "ServeEngine(autotune=True) needs a CompressedModel "
                        "— raw parameter pytrees carry no compiled leaves "
                        "to tune")
                kw = {} if autotune_options is None else \
                    {"options": autotune_options}
                table = autotune_model(cm, M=batch_slots, **kw)
            dispatch = _dc.replace(dispatch, tuned=table,
                                   m_bucket=batch_slots)
        self.params = params
        self.patterns = patterns
        self.dispatch = dispatch
        self.cfg = cfg
        self.slots = batch_slots
        self.max_len = max_len
        self.kv_cache = kv_cache
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.packed_read = packed_read
        self._chunked = cfg.family in _CHUNKED_FAMILIES
        # kv tile rows of the fused read — resolved ONCE (tuned entry when
        # available, default otherwise) and pinned, so every step of a
        # sequence walks the same tiles whatever its extent bucket
        self._bt = None
        if kv_cache in ("int4", "int4x2"):
            self._bt = ATTN_BT_DEFAULT
            if table is not None and self._chunked:
                from ..core.autotune import TuneOptions, autotune_attn
                opts = autotune_options or TuneOptions()
                winner = autotune_attn(
                    B=batch_slots, T=max_len, H=cfg.n_heads,
                    Hkv=cfg.n_kv_heads, Dh=cfg.head_dim,
                    options=opts, table=table)
                self._bt = winner.bm or ATTN_BT_DEFAULT
        self.cache = init_cache(cfg, batch_slots, max_len, kv_cache=kv_cache)
        self._fresh = init_cache(cfg, batch_slots, max_len, kv_cache=kv_cache)
        self._batch_axes = cache_batch_axes(cfg, kv_cache=kv_cache)
        self.active: Dict[int, Request] = {}
        self.prompt_pos: Dict[int, int] = {}
        self.remaining: Dict[int, int] = {}
        self.last_tok = np.zeros((batch_slots, 1), np.int32)
        self.queue: List[Request] = []
        self._unreturned: List[Request] = []
        self.steps_run = 0
        # chunked-interleave state (attention-only families)
        self._phase: Dict[int, str] = {}     # slot -> "prefill" | "decode"
        self._len = np.zeros(batch_slots, np.int64)  # host mirror of length
        self._order: List[int] = []          # prefill FIFO (admission order)
        self._stats = {"prefill_steps": 0, "decode_steps": 0,
                       "prefill_tokens": 0, "decode_tokens": 0,
                       "prefill_ms": [], "decode_ms": []}
        self._decode_fns: Dict[int, object] = {}   # t_bound -> jitted step
        self._prefill_fns: Dict[int, object] = {}
        self._step = jax.jit(
            lambda p, c, t: decode_step(p, cfg, c, t, patterns=patterns,
                                        dispatch=dispatch))

    def submit(self, req: Request):
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        # positions written = prompt + generated-but-one (the last generated
        # token is returned without being fed back)
        needed = len(req.prompt) + max(0, req.max_new_tokens - 1)
        if needed > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt ({len(req.prompt)} tokens) + "
                f"max_new_tokens ({req.max_new_tokens}) needs {needed} cache "
                f"positions but max_len is {self.max_len} — the cache would "
                "silently wrap; raise max_len or trim the request")
        req.out = []
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        self._unreturned.append(req)

    def cache_bytes(self) -> int:
        """Resident bytes of the decode cache (all leaves, scales
        included) — the serving-memory number BENCH_serve records."""
        return sum(int(leaf.nbytes)
                   for leaf in jax.tree_util.tree_leaves(self.cache))

    def stats(self) -> Dict:
        """Per-phase counters: step counts, token counts, and per-step
        wall-clock (ms) lists — benches/tests read phase timings here
        instead of re-deriving them from the outside."""
        out = dict(self._stats)
        out["prefill_ms"] = list(self._stats["prefill_ms"])
        out["decode_ms"] = list(self._stats["decode_ms"])
        return out

    def tokens_processed(self) -> int:
        """Total tokens pushed through the model (prefill + decode) —
        the throughput numerator serve benches use."""
        return int(self._stats["prefill_tokens"]
                   + self._stats["decode_tokens"])

    def _reset_slot(self, slot: int):
        """Zero one slot's cache by splicing in the fresh (zero) values.

        The batch axis differs per leaf family — attention leaves stack as
        (L, B, ...), inner-vmapped SSM leaves as (L, inner, B, ...) — so
        each leaf's slot axis comes from the explicit
        :func:`repro.models.model.cache_batch_axes` spec.  (Guessing the
        axis by size sliced the wrong axis whenever a stacked non-batch
        axis matched ``batch_slots``, e.g. hybrid ``attn_every == slots``
        leaked a stale KV cache into admitted requests.)"""
        def reset(cur, fresh, ax):
            idx = [slice(None)] * cur.ndim
            idx[ax] = slot
            return cur.at[tuple(idx)].set(fresh[tuple(idx)])
        self.cache = jax.tree_util.tree_map(reset, self.cache, self._fresh,
                                            self._batch_axes)

    def _chunk_fits(self, req: Request) -> bool:
        """Can the chunk schedule write without clamping?  The final
        (possibly ragged) chunk still writes ``prefill_chunk`` rows from
        its start offset, so the rounded-up prompt must fit the cache."""
        C = self.prefill_chunk
        return -(-len(req.prompt) // C) * C <= self.max_len

    def _admit(self):
        free = [s for s in range(self.slots) if s not in self.active]
        while free and self.queue:
            slot = free.pop(0)
            req = self.queue.pop(0)
            self._reset_slot(slot)
            self.active[slot] = req
            self.remaining[slot] = req.max_new_tokens
            self._len[slot] = 0
            if self._chunked and self._chunk_fits(req):
                self._phase[slot] = "prefill"
                self.prompt_pos[slot] = 0
                self._order.append(slot)
            else:
                # legacy token drip (non-attention families, or a prompt
                # whose rounded-up chunk schedule overruns the cache)
                self._phase[slot] = "decode"
                self.prompt_pos[slot] = 1
                self.last_tok[slot, 0] = int(req.prompt[0])

    # ------------------------------------------------- chunked interleave

    def _bucket_t(self, t: int) -> int:
        """Power-of-two cache-read extent covering ``t`` positions (floor
        32, capped at max_len) — one jitted step per bucket; every
        bucket attends the same live tiles (dead tiles / masked extents)."""
        b = 32
        while b < t:
            b *= 2
        return min(b, self.max_len)

    def _decode_fn(self, tb: int):
        fn = self._decode_fns.get(tb)
        if fn is None:
            cfg, patterns, dispatch = self.cfg, self.patterns, self.dispatch
            bt, pr = self._bt, self.packed_read
            fn = jax.jit(lambda p, c, t, a: decode_step(
                p, cfg, c, t, patterns=patterns, dispatch=dispatch,
                active=a, t_bound=tb, bt=bt, packed_read=pr))
            self._decode_fns[tb] = fn
        return fn

    def _prefill_fn(self, tb: int):
        """Jitted one-slot chunk prefill: gather the slot's batch-of-one
        cache view, run the chunk, scatter it back.  The slot index is a
        traced scalar — one compile per extent bucket."""
        fn = self._prefill_fns.get(tb)
        if fn is None:
            cfg, patterns, dispatch = self.cfg, self.patterns, self.dispatch
            bt, pr, axes = self._bt, self.packed_read, self._batch_axes

            def gather(cache, slot):
                return jax.tree_util.tree_map(
                    lambda leaf, ax: jax.lax.dynamic_slice_in_dim(
                        leaf, slot, 1, axis=ax), cache, axes)

            def scatter(cache, sub, slot):
                return jax.tree_util.tree_map(
                    lambda leaf, s, ax: jax.lax.dynamic_update_slice_in_dim(
                        leaf, s, slot, axis=ax), cache, sub, axes)

            def f(p, cache, slot, toks, nv):
                sub = gather(cache, slot)
                logits, sub = prefill_step(
                    p, cfg, sub, toks, patterns=patterns, dispatch=dispatch,
                    n_valid=nv, t_bound=tb, bt=bt, packed_read=pr)
                return logits, scatter(cache, sub, slot)

            fn = jax.jit(f)
            self._prefill_fns[tb] = fn
        return fn

    def _finish(self, slot: int, now: float) -> bool:
        """Free a slot whose budget is exhausted; True when freed."""
        if self.remaining[slot] > 0:
            return False
        req = self.active[slot]
        req.t_done = now
        del self.active[slot], self.remaining[slot], self.prompt_pos[slot]
        self._phase.pop(slot, None)
        return True

    def _step_prefill(self):
        """Advance the oldest prefilling slot by one chunk."""
        slot = self._order[0]
        req = self.active[slot]
        C = self.prefill_chunk
        pos = self.prompt_pos[slot]
        nv = min(C, len(req.prompt) - pos)
        toks = np.zeros((1, C), np.int32)
        toks[0, :nv] = req.prompt[pos:pos + nv]
        tb = self._bucket_t(int(self._len[slot]) + C)
        fn = self._prefill_fn(tb)
        t0 = time.perf_counter()
        logits, self.cache = fn(self.params, self.cache,
                                jnp.asarray(slot, jnp.int32),
                                jnp.asarray(toks),
                                jnp.asarray([nv], jnp.int32))
        logits = np.asarray(logits)  # sync for honest phase timing
        now = time.perf_counter()
        self._stats["prefill_steps"] += 1
        self._stats["prefill_tokens"] += nv
        self._stats["prefill_ms"].append((now - t0) * 1e3)
        self.prompt_pos[slot] = pos + nv
        self._len[slot] += nv
        if self.prompt_pos[slot] == len(req.prompt):
            # prompt complete: the first generated token IS the final
            # chunk's last valid row — no separate decode step (TTFT win)
            self._order.pop(0)
            self._phase[slot] = "decode"
            if self.remaining[slot] > 0:
                nxt = int(np.argmax(logits[0, nv - 1]))
                self.last_tok[slot, 0] = nxt
                req.out.append(nxt)
                req.t_first = now
                self.remaining[slot] -= 1
            self._finish(slot, now)

    def _step_decode(self, dec_slots: List[int]):
        """One generated (or dripped prompt) token for every decoding
        slot; prefilling/idle slots are masked out via ``active``."""
        act = np.zeros(self.slots, np.int32)
        act[dec_slots] = 1
        tb = self._bucket_t(max(int(self._len[s]) for s in dec_slots) + 1)
        fn = self._decode_fn(tb)
        t0 = time.perf_counter()
        logits, self.cache = fn(self.params, self.cache,
                                jnp.asarray(self.last_tok),
                                jnp.asarray(act))
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        now = time.perf_counter()
        self._stats["decode_steps"] += 1
        self._stats["decode_tokens"] += len(dec_slots)
        self._stats["decode_ms"].append((now - t0) * 1e3)
        for slot in dec_slots:
            req = self.active[slot]
            self._len[slot] += 1
            pos = self.prompt_pos[slot]
            if pos < len(req.prompt):
                # drip fallback: still feeding the prompt
                self.last_tok[slot, 0] = int(req.prompt[pos])
                self.prompt_pos[slot] = pos + 1
                continue
            if self.remaining[slot] > 0:
                self.last_tok[slot, 0] = int(nxt[slot])
                req.out.append(int(nxt[slot]))
                if req.t_first is None:
                    req.t_first = now
                self.remaining[slot] -= 1
            self._finish(slot, now)

    def _step_chunked(self) -> int:
        self._admit()
        if not self.active:
            return 0
        # snapshot the decode set BEFORE the prefill advances: a slot
        # finishing its prompt this step already got its first token from
        # the chunk logits and starts decoding next step
        dec_slots = sorted(s for s, ph in self._phase.items()
                           if ph == "decode" and s in self.active)
        if self._order:
            self._step_prefill()
        if dec_slots:
            self._step_decode(dec_slots)
        self.steps_run += 1
        # a zero-budget request that finished during prefill may have
        # freed a slot; admitting here keeps run() from spinning on an
        # empty active set while the queue is non-empty
        return len(self.active)

    # ---------------------------------------------------- legacy token drip

    def _step_legacy(self) -> int:
        self._admit()
        if not self.active:
            return 0
        t0 = time.perf_counter()
        logits, self.cache = self._step(self.params, self.cache,
                                        jnp.asarray(self.last_tok))
        self.steps_run += 1
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        now = time.perf_counter()
        self._stats["decode_steps"] += 1
        self._stats["decode_tokens"] += len(self.active)
        self._stats["decode_ms"].append((now - t0) * 1e3)
        done = []
        for slot, req in self.active.items():
            pos = self.prompt_pos[slot]
            if pos < len(req.prompt):
                # still prefilling: feed the next prompt token
                self.last_tok[slot, 0] = int(req.prompt[pos])
                self.prompt_pos[slot] = pos + 1
            else:
                # generate only while budget remains: a request admitted
                # with max_new_tokens=0 finishes right after prefill with
                # out == [] (the decrement used to run after the append,
                # so every request emitted at least one token)
                if self.remaining[slot] > 0:
                    self.last_tok[slot, 0] = int(nxt[slot])
                    req.out.append(int(nxt[slot]))
                    if req.t_first is None:
                        req.t_first = now
                    self.remaining[slot] -= 1
                if self.remaining[slot] <= 0:
                    done.append(slot)
        for slot in done:
            self.active[slot].t_done = now
            del self.active[slot], self.remaining[slot], self.prompt_pos[slot]
            self._phase.pop(slot, None)
        return len(self.active)

    def step(self) -> int:
        if self._chunked:
            return self._step_chunked()
        return self._step_legacy()

    def run(self) -> List[Request]:
        """Drain the engine; returns every request submitted since the
        last ``run()`` — including ones a prior ``step()`` call already
        admitted or finished (the old queue snapshot dropped those)."""
        while self.queue or self.active:
            self.step()
        out, self._unreturned = self._unreturned, []
        return out
