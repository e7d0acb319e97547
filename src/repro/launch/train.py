"""Training launcher: real end-to-end driver on whatever devices exist.

Composes every substrate layer: config registry -> data pipeline -> sharded
train state -> pjit'd train step -> fault-tolerant loop with async
checkpointing, preemption handling, straggler monitoring, and elastic
restore (mesh-agnostic checkpoints re-shard onto the current topology).

  PYTHONPATH=src python -m repro.launch.train --arch amr-paper-100m \
      --reduced --steps 100 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config, get_reduced_config
from repro.data import SyntheticLM
from repro.launch.cli import add_numerics_args, apply_pallas_interpret, numerics_from_args
from repro.launch.mesh import make_host_mesh
from repro.numerics import root_key
from repro.parallel import sharding as shard_lib
from repro.runtime import FaultTolerantLoop, Heartbeat
from repro.train.steps import make_train_state, make_train_step


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="amr-paper-100m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--tp", type=int, default=1, help="model-parallel size")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatch", type=int, default=0)
    add_numerics_args(ap)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    apply_pallas_interpret(args, tag="train")
    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    nm = numerics_from_args(args)
    if nm is not None:
        from repro.launch.cli import policy_label

        cfg = dataclasses.replace(cfg, numerics=nm)
        print(f"[train] numerics policy: {policy_label(nm)}")

    mesh = make_host_mesh(model_parallel=args.tp)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                       seed=args.seed)
    step_raw = make_train_step(cfg, peak_lr=args.lr, warmup=20,
                               total_steps=args.steps,
                               microbatch=args.microbatch or None)

    def make_state():
        with jax.set_mesh(mesh):
            state = make_train_state(cfg, root_key(args.seed))
            specs = shard_lib.param_specs(mesh, state, cfg)
            sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                              is_leaf=lambda x: isinstance(x, P))
            return jax.device_put(state, sh)

    def remesh(host_state):
        # elastic restart: re-shard a (host-side) restored state onto the
        # mesh we have NOW (may differ from the saving run's topology)
        specs = shard_lib.param_specs(mesh, host_state, cfg)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(host_state, sh)

    jitted = jax.jit(step_raw, donate_argnums=(0,))

    def step_fn(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with jax.set_mesh(mesh):
            return jitted(state, batch)

    hb = Heartbeat(Path(args.ckpt_dir) / "heartbeat.json")
    hb.start()
    loop = FaultTolerantLoop(
        ckpt_dir=args.ckpt_dir, make_state=make_state, step_fn=step_fn,
        batch_at=data.batch_at, ckpt_every=args.ckpt_every, remesh=remesh,
        heartbeat=hb)
    loop.install_preemption_handler()
    t0 = time.time()
    result = loop.run(args.steps)
    hb.stop()
    tok_s = result.steps_done * args.batch * args.seq / max(time.time() - t0, 1e-9)
    print(f"[train] done: {result.steps_done} steps, {result.restarts} restarts, "
          f"preempted={result.preempted}, ~{tok_s:.0f} tok/s")


if __name__ == "__main__":
    main()
