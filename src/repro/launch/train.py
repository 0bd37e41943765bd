"""Production training launcher.

On a real TPU pod this binary runs per host (jax.distributed initializes
from the cluster env); in this container it runs the same code path on the
local mesh with a reduced config unless --full is given.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 100 --ckpt /tmp/ckpt [--batch 8 --seq 256] [--full]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config, reduced_config
from ..data.synthetic import token_batch
from ..models.model import init_params
from ..train.optimizer import AdamWConfig, adamw_init
from ..train.runtime import RunnerConfig, TrainRunner
from ..train.trainer import make_train_step, pick_n_micro
from .compile_cache import enable_compile_cache
from .mesh import data_axes, make_local_mesh, make_production_mesh, mesh_size
from .sharding import batch_specs, param_specs, sanitize_specs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="full config + production mesh (TPU pod)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--step-deadline", type=float, default=0.0,
                    help="straggler watchdog seconds (0 = off)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.full:
        cfg = get_config(args.arch)
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        cfg = reduced_config(args.arch)
        mesh = make_local_mesh()
    if cfg.frontend:
        raise SystemExit("frontend archs: use examples/ drivers with "
                         "precomputed embeddings")

    dp = 1
    for a in data_axes(mesh):
        dp *= mesh_size(mesh, a)
    n_micro = pick_n_micro(cfg, args.batch, dp)

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          state_dtype=cfg.opt_state_dtype)
    opt = adamw_init(params, opt_cfg)

    pspecs = sanitize_specs(param_specs(params, cfg, mesh), params, mesh)
    p_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    with jax.sharding.set_mesh(mesh):
        params = jax.tree_util.tree_map(jax.device_put, params, p_shard)
        step = jax.jit(make_train_step(cfg, opt_cfg, n_micro))

        def data_fn(i):
            toks, labels = token_batch(i, args.batch, args.seq, cfg.vocab)
            return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}

        opt_shard = {"m": p_shard, "v": p_shard,
                     "step": NamedSharding(mesh, P())}
        runner = TrainRunner(step, data_fn, RunnerConfig(
            total_steps=args.steps, ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt, step_deadline_s=args.step_deadline,
            log_every=10), shardings={"params": p_shard, "opt": opt_shard})
        runner.run(params, opt)
    print("[train] done")


if __name__ == "__main__":
    main()
