import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.
_DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM and unsupported collectives all fail here.
Records memory_analysis / cost_analysis / collective-bytes per cell into a
JSON cache consumed by the roofline benchmark and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out FILE]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config
from ..models.config import SHAPES
from ..train.optimizer import AdamWConfig
from ..train.trainer import make_prefill_step, make_serve_step, make_train_step, pick_n_micro
from .hlo_analysis import analyse_hlo, roofline_terms
from .mesh import data_axes, make_production_mesh, mesh_size
from .sharding import batch_specs, cache_specs, param_specs, sanitize_specs
from .specs import cache_shapes, input_specs, opt_shapes, param_shapes

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def apply_variant(cfg, variant: str):
    """Hillclimb variants: '+'-separated config mutations.

    int8      — int8 weight storage for every linear (QNN datapath)
    seqshard  — sequence/context parallelism for activations & attention
    nmicroN   — override gradient-accumulation microbatch count
    noremat   — disable activation checkpointing
    """
    import dataclasses
    n_micro_override = None
    flags = {"fsdp": True}
    for tok in variant.split("+"):
        if tok in ("", "baseline"):
            continue
        elif tok == "int8":
            cfg = dataclasses.replace(cfg, linear_mode="int8")
        elif tok.startswith("gsparseint8"):
            dens = float(tok[len("gsparseint8"):] or 50) / 100
            cfg = dataclasses.replace(cfg, linear_mode="gsparse_int8",
                                      sparse_density=dens)
        elif tok.startswith("gsparse"):
            dens = float(tok[len("gsparse"):] or 50) / 100
            cfg = dataclasses.replace(cfg, linear_mode="gsparse",
                                      sparse_density=dens)
        elif tok.startswith("sparseint8"):
            dens = float(tok[len("sparseint8"):] or 50) / 100
            cfg = dataclasses.replace(cfg, linear_mode="sparse_int8",
                                      sparse_density=dens)
        elif tok.startswith("sparse"):
            dens = float(tok[len("sparse"):] or 50) / 100
            cfg = dataclasses.replace(cfg, linear_mode="sparse",
                                      sparse_density=dens)
        elif tok == "seqshard":
            cfg = dataclasses.replace(cfg, seq_shard=True)
        elif tok == "noremat":
            cfg = dataclasses.replace(cfg, remat=False)
        elif tok == "nofsdp":
            flags["fsdp"] = False
        elif tok.startswith("nmicro"):
            n_micro_override = int(tok[len("nmicro"):])
        else:
            raise ValueError(f"unknown variant token {tok!r}")
    return cfg, n_micro_override, flags


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               variant: str = "baseline"):
    """Returns (lowered, compiled, meta) for one cell."""
    cfg = get_config(arch)
    cfg, n_micro_override, flags = apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    if shape not in cfg.applicable_shapes():
        return None, None, {"skipped": True, "reason": _skip_reason(cfg, shape_name)}
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_size = 1
    for a in data_axes(mesh):
        dp_size *= mesh_size(mesh, a)

    pshapes = param_shapes(cfg)
    pspecs = sanitize_specs(
        param_specs(pshapes, cfg, mesh, fsdp=flags["fsdp"]), pshapes, mesh)
    p_shard = _ns(mesh, pspecs)
    binputs = input_specs(cfg, shape)
    bspecs = sanitize_specs(_filter_batch(batch_specs(cfg, mesh), binputs),
                            binputs, mesh)
    b_shard = _ns(mesh, bspecs)

    t0 = time.time()
    if shape.kind == "train":
        opt_cfg = AdamWConfig(state_dtype=cfg.opt_state_dtype)
        oshapes = opt_shapes(cfg, pshapes, opt_cfg)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        o_shard = _ns(mesh, ospecs)
        n_micro = n_micro_override or pick_n_micro(cfg, shape.global_batch,
                                                   dp_size)
        step = make_train_step(cfg, opt_cfg, n_micro)
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(pshapes, oshapes, input_specs(cfg, shape))
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                         out_shardings=None)
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(pshapes, input_specs(cfg, shape))
    else:  # decode
        cshapes = cache_shapes(cfg, shape)
        cspecs = sanitize_specs(
            cache_specs(cfg, mesh, batch=shape.global_batch), cshapes, mesh)
        c_shard = _ns(mesh, cspecs)
        step = make_serve_step(cfg)
        dp = data_axes(mesh)
        tok_spec = P(dp if len(dp) > 1 else dp[0], None)
        if shape.global_batch % dp_size:
            tok_spec = P(None, None)
        tok_shard = NamedSharding(mesh, tok_spec)
        jitted = jax.jit(step, in_shardings=(p_shard, c_shard, tok_shard),
                         out_shardings=(None, c_shard))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(pshapes, cshapes,
                                   input_specs(cfg, shape)["tokens"])
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    meta = {"t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
            "n_micro": pick_n_micro(cfg, shape.global_batch, dp_size)
            if shape.kind == "train" else None}
    return lowered, compiled, meta


def _skip_reason(cfg, shape_name):
    if not cfg.supports_decode:
        return "encoder-only: no decode step exists"
    return "full-attention arch: 512k decode requires sub-quadratic attention"


def _filter_batch(spec_tree, inputs):
    return {k: v for k, v in spec_tree.items() if k in inputs}


def analyse(lowered, compiled, *, n_chips: int, cfg=None, shape=None) -> dict:
    # raw XLA numbers (while bodies counted ONCE — kept for reference)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    mem_fields = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes"):
        mem_fields[f] = getattr(mem, f, None)

    # while-aware re-analysis (see hlo_analysis.py): trip counts folded in
    hlo = compiled.as_text()
    h = analyse_hlo(hlo)
    flops = h["flops"]
    traffic = h["traffic_bytes"]
    coll_total = h["collective_bytes"]
    terms = roofline_terms(flops, traffic, coll_total, n_chips=n_chips)

    rec = {
        "flops_per_device": flops,
        "traffic_bytes_per_device": traffic,
        "traffic_upper_bytes_per_device": h["traffic_upper_bytes"],
        "traffic_by_scope": h["traffic_by_scope"],
        "collective_bytes_per_device": coll_total,
        "collectives": h["collectives"],
        "unknown_trip_whiles": h["unknown_trip_whiles"],
        "xla_cost_analysis_raw": {"flops": raw_flops, "bytes": raw_bytes},
        "memory_analysis": mem_fields,
        "roofline": terms,
    }
    # flash adjustment: the Pallas flash-attention kernel keeps score
    # tensors in VMEM — replace attention-scoped dot traffic with the
    # kernel's linear q/k/v/o streaming (kernels/flash_attention, validated
    # in interpret mode).  Reported alongside the XLA-attention roofline.
    attn_traffic = sum(v for k, v in h["traffic_by_scope"].items()
                       if "attention" in k)
    if attn_traffic > 0 and cfg is not None and shape is not None:
        B = shape.global_batch
        T = shape.seq_len if shape.kind != "decode" else 1
        Dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        if cfg.family == "hybrid":
            L_attn = cfg.n_layers // max(cfg.attn_every, 1)
        elif cfg.family == "ssm":
            L_attn = 0
        else:
            L_attn = cfg.n_layers
        passes = 3.0 if shape.kind == "train" else 1.0
        kv_T = shape.seq_len  # decode reads the whole cache
        flash_io = (B * (2 * T * H * Dh + 2 * kv_T * Hkv * Dh) * 2.0
                    * L_attn * passes) / n_chips
        traffic_flash = traffic - attn_traffic + flash_io
        rec["roofline_flash"] = roofline_terms(
            flops, traffic_flash, coll_total, n_chips=n_chips)
        rec["attention_traffic_bytes"] = attn_traffic
    if cfg is not None and shape is not None:
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        n = cfg.active_param_count()
        mult = 6.0 if shape.kind == "train" else 2.0
        model_flops = mult * n * tokens
        rec["model_flops_global"] = model_flops
        global_hlo = flops * n_chips
        rec["model_flops_ratio"] = model_flops / global_hlo if global_hlo else None
    return rec


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Path,
             force: bool = False, variant: str = "baseline") -> dict:
    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    if variant != "baseline":
        tag += f"__{variant.replace('+', '_')}"
    out_file = out_dir / f"{tag}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())
    n_chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "n_chips": n_chips, "variant": variant}
    try:
        lowered, compiled, meta = lower_cell(arch, shape_name,
                                             multi_pod=multi_pod,
                                             variant=variant)
        rec.update(meta)
        if lowered is not None:
            rec.update(analyse(lowered, compiled, n_chips=n_chips,
                               cfg=get_config(arch), shape=SHAPES[shape_name]))
            rec["status"] = "ok"
        else:
            rec["status"] = "skipped"
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_dir.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out_dir = Path(args.out)

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    for a, s, mp in cells:
        t0 = time.time()
        rec = run_cell(a, s, multi_pod=mp, out_dir=out_dir, force=args.force,
                       variant=args.variant)
        status = rec.get("status")
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" bound={r['bound']} total={r['total']:.3e}s"
                     f" compile={rec.get('t_compile_s')}s")
        elif status == "error":
            extra = " " + rec.get("error", "")[:120]
        print(f"[{time.strftime('%H:%M:%S')}] {a} × {s} × "
              f"{'2pod' if mp else '1pod'}: {status}{extra} "
              f"({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
