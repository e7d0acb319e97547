#!/usr/bin/env python3
"""Chip smoke: amr-paper-100m at its full published width on one TPU.

Drives the system's main path once, through the entry points a user calls,
and checks what comes out against references computed on the same chip.
Phases, in order, each printing one line:

  device   the backend is a TPU and Pallas kernels run compiled;
  kernels  each Pallas kernel the model dispatches on TPU, compiled, at the
           model's matmul shapes (q/k/v/o 768->768, MLP 768->3072->768, LM
           head 768->32000; attention's grouped QK^T / PV) for 256 prefill
           and 4 decode rows, against its reference;
  train    5 steps of ``make_train_step`` under ``jax.jit`` with the state
           donated, as launch/train.py runs it: batch 8 x 1024 SyntheticLM
           tokens, under ``exact`` and the config's amr_lowrank(b=8, r=16);
           every loss finite;
  serve    ``ServeEngine`` with 4 slots, 8 requests of 128 prompt + 32 new
           tokens, under ``exact`` and amr_kernel(b=8, r=16) — the Pallas
           low-rank kernel in every weight matmul; a request decoded in the
           busy engine gives the tokens it gets when served alone.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed; a failed phase exits non-zero.  The times and
memory figures on the phase lines are smoke readings from the chip, not
benchmark numbers.  Weights and data are random, made from seed 0.

    python chip_smoke.py             # one chip: the phases above
    python chip_smoke.py --chips 4   # only sharded training: a (data 2,
                                     # model 2) mesh against one device

Where ``JAX_COMPILATION_CACHE_DIR`` is unset, compiled programs are cached
in ``.jax_cache`` next to this file.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

try:
    from repro.configs.registry import get_config  # noqa: E402
except ModuleNotFoundError as e:
    sys.exit(f"chip_smoke: cannot import the repro package from {REPO / 'src'} "
             f"({e}); run this script from a checkout of the repository")

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import engine as engine_lib  # noqa: E402
from repro.core import lut as lut_lib  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.kernels import pallas_config  # noqa: E402
from repro.kernels.amr_matmul.kernel import (amr_matmul_int8_lut,  # noqa: E402
                                             amr_matmul_int8_lut_grouped)
from repro.kernels.amr_matmul.ops import amr_matmul, lut_factors  # noqa: E402
from repro.kernels.amr_matmul.ref import ref_lowrank_int8  # noqa: E402
from repro.kernels.inject_replay import inject_replay_matmul  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.numerics import AMRNumerics, root_key  # noqa: E402
from repro.numerics.approx_matmul import matmul_amr_lowrank  # noqa: E402
from repro.parallel import sharding as shard_lib  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.train.steps import make_train_state, make_train_step  # noqa: E402

ARCH = "amr-paper-100m"
SEED = 0
BORDER, RANK = 8, 16
# kernel vs references, relative to max |reference|: the same f32 math
# differs only in summation order; matmul_amr_lowrank rounds the looked-up
# error factors to bf16 (2**-9 relative each), the kernel keeps them f32.
LOWRANK_F32_RTOL = 1e-5
LOWRANK_XLA_RTOL = 1e-3
# sharded vs one-device training losses: the mesh changes f32 reduction
# order, which can move an operand across an int8 rounding boundary of the
# AMR quantizer; 5 steps of that stay far below 1e-2 of the loss.
SHARDED_LOSS_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase's output did not meet its check."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _peak_gib() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2**30:.3f}GiB"


def _say(phase: str, body: dict) -> None:
    print(f"[{phase}] " + json.dumps(body, separators=(",", ":")), flush=True)


def _progress(msg: str) -> None:
    """Where a run got to, on stderr (stdout keeps one line per phase)."""
    print(f"chip_smoke: {time.strftime('%H:%M:%S')} {msg}", file=sys.stderr,
          flush=True)


# --------------------------------------------------------------- device
def phase_device(chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    _check(dev.platform == "tpu",
           f"device: no TPU — JAX found platform {dev.platform!r} "
           f"({dev.device_kind}, {len(devs)} device(s))")
    _check(len(devs) >= chips, f"device: {chips} chips asked, {len(devs)} found")
    forced = os.environ.get(pallas_config.ENV_VAR, "")
    _check(pallas_config.resolve_interpret(None) is False,
           f"device: Pallas would run in interpret mode on the TPU "
           f"({pallas_config.ENV_VAR}={forced!r})")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    _say("device", {**info, "pallas_interpret": False})
    return info


# -------------------------------------------------------------- kernels
def _grid_operands(rng, shape, axis):
    """Random int8-grid values as f32, with a +-127 in every row (axis=1)
    or column (axis=0), so both seam quantizers see scale 1 and the same
    int8 operands."""
    x = rng.integers(-127, 128, shape)
    if axis == 1:
        x[:, 0] = 127
    else:
        x[0, :] = -127
    return jnp.asarray(x, jnp.float32), jnp.asarray(x, jnp.int8)


@jax.jit
def _lut_oracle(ia, ib, table):
    """The amr_lut oracle's integer core: gather every product, sum K."""
    return jnp.sum(table[ia.astype(jnp.int32)[..., :, :, None] + 128,
                         ib.astype(jnp.int32)[..., None, :, :] + 128],
                   axis=-2, dtype=jnp.int32)


@jax.jit
def _lowrank_f32(qa, qb, u, v):
    """The low-rank kernel's math in f32 XLA (kernels/amr_matmul/ref.py)."""
    with jax.default_matmul_precision("highest"):
        return ref_lowrank_int8(qa, qb, u, v)


_lowrank_xla = jax.jit(lambda a, b: matmul_amr_lowrank(a, b, BORDER, RANK))


def phase_kernels(cfg, rows=(256, 4), oracle_rows: int = 4) -> dict:
    """Every TPU-dispatched kernel at the model's shapes vs its reference.

    2-D LUT oracles materialise (rows, K, N), so they cover the first and
    last ``oracle_rows`` rows of each output (rows are independent)."""
    rng = np.random.default_rng(SEED)
    d, h = cfg.d_model, cfg.n_heads * cfg.head_dim
    shapes = {"qkvo": (d, h), "mlp_up": (d, cfg.d_ff),
              "mlp_down": (cfg.d_ff, d), "lm_head": (d, cfg.vocab)}
    u, v = lut_factors(BORDER, RANK)
    table = lut_lib.table_array(BORDER)
    lowrank, lut, worst_f32, worst_xla = 0, 0, 0.0, 0.0
    t0 = time.perf_counter()
    for m in rows:
        for name, (k, n) in shapes.items():
            _progress(f"kernels {name} M={m}")
            a, qa = _grid_operands(rng, (m, k), 1)
            b, qb = _grid_operands(rng, (k, n), 0)
            got = amr_matmul(a, b, border=BORDER, rank=RANK, method="lowrank")
            ref = _lowrank_f32(qa, qb, u, v)
            xla = _lowrank_xla(a, b)
            scale = float(jnp.max(jnp.abs(ref)))
            e_f32 = float(jnp.max(jnp.abs(got - ref))) / scale
            e_xla = float(jnp.max(jnp.abs(got - xla))) / scale
            _check(e_f32 <= LOWRANK_F32_RTOL and e_xla <= LOWRANK_XLA_RTOL,
                   f"kernels: lowrank {name} M={m}: rel err {e_f32:.3g} vs "
                   f"f32 reference, {e_xla:.3g} vs matmul_amr_lowrank")
            worst_f32, worst_xla = max(worst_f32, e_f32), max(worst_xla, e_xla)
            lowrank += 1

            got = amr_matmul_int8_lut(qa, qb, table)
            for sl in (slice(0, oracle_rows), slice(m - oracle_rows, m)):
                want = _lut_oracle(qa[sl], qb, table)
                _check(bool(jnp.array_equal(got[sl], want)),
                       f"kernels: lut {name} M={m} rows {sl}: not bit-exact")
            lut += 1
    grouped = 0
    for m in rows:  # attention: QK^T (M, hd) @ (hd, T) and PV (M, T) @ (T, hd)
        t = max(rows)
        for name, (k, n) in {"qk": (cfg.head_dim, t), "pv": (t, cfg.head_dim)}.items():
            qa = jnp.asarray(rng.integers(-128, 128, (cfg.n_heads, m, k)), jnp.int8)
            qb = jnp.asarray(rng.integers(-128, 128, (cfg.n_heads, k, n)), jnp.int8)
            got = amr_matmul_int8_lut_grouped(qa, qb, table)
            _check(bool(jnp.array_equal(got, _lut_oracle(qa, qb, table))),
                   f"kernels: lut_grouped {name} M={m}: not bit-exact")
            grouped += 1
    # inject_replay is refused by the TPU compiler: asked for compiled, it
    # must say so by name, and amr_inject must not pick it by default
    _check(pallas_config.default_inject_impl() == "xla",
           "kernels: amr_inject does not default to the XLA replay")
    inj = engine_lib.get_injector(2, BORDER)
    ia = jnp.full((8, 128), 128, jnp.int32)
    try:
        inject_replay_matmul(inj, ia, ia.T, interpret=False)
    except pallas_config.KernelRefusedError as e:
        _check("inject_replay" in str(e), f"kernels: refusal unnamed: {e}")
    else:
        raise SmokeFailure("kernels: inject_replay ran; it is recorded as "
                           "refused on TPU (pallas_config.REFUSED_ON_TPU)")
    body = {"lowrank": lowrank, "lowrank_rel_err_f32": worst_f32,
            "lowrank_rel_err_xla": worst_xla, "lut_bitexact": lut,
            "lut_grouped_bitexact": grouped, "inject_replay": "refused",
            "rows": list(rows), "seconds": round(time.perf_counter() - t0, 3)}
    _say("kernels", body)
    return body


# ---------------------------------------------------------------- train
def train_run(cfg, mesh, *, steps: int, batch: int, seq: int) -> dict:
    """``steps`` train steps the way launch/train.py runs them: state
    placed by the sharding rules on ``mesh``, ``jax.jit`` with the state
    donated.  Any exception ends the run (no restart)."""
    _progress(f"train {cfg.numerics.mode} on mesh {dict(mesh.shape)}")
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, batch=batch, seed=SEED)
    step = jax.jit(make_train_step(cfg, peak_lr=3e-3, warmup=20,
                                   total_steps=steps), donate_argnums=(0,))
    with jax.set_mesh(mesh):
        state = make_train_state(cfg, root_key(SEED))
        specs = shard_lib.param_specs(mesh, state, cfg)
        state = jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))
        batches = [{k: jnp.asarray(x) for k, x in data.batch_at(i).items()}
                   for i in range(steps)]
        t0 = time.perf_counter()
        compiled = step.lower(state, batches[0]).compile()
        compile_s = time.perf_counter() - t0
        losses, seconds = [], []
        for b in batches:
            t0 = time.perf_counter()
            state, metrics = compiled(state, b)
            losses.append(float(metrics["loss"]))     # blocks on the step
            seconds.append(time.perf_counter() - t0)
    _check(all(math.isfinite(x) for x in losses),
           f"train: non-finite loss under {cfg.numerics.mode}: {losses}")
    return {"losses": losses, "compile_s": compile_s, "step_s": seconds,
            "params": state.params}


def phase_train(cfg, *, steps: int = 5, batch: int = 8, seq: int = 1024) -> dict:
    mesh = make_host_mesh()
    out = {}
    for nm in (AMRNumerics("exact"), cfg.numerics):
        run = train_run(dataclasses.replace(cfg, numerics=nm), mesh,
                        steps=steps, batch=batch, seq=seq)
        run.pop("params")
        out[nm.mode] = {**run, "peak": _peak_gib()}
    _say("train", {"batch": batch, "seq": seq, "steps": steps, **out})
    return out


def _param_spread(params) -> dict:
    """Bytes of parameter shards held by each device."""
    per: dict[str, int] = {}
    split = 0
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per[str(shard.device.id)] = per.get(str(shard.device.id), 0) + shard.data.nbytes
        split += leaf.addressable_shards[0].data.shape != leaf.shape
    return {"bytes_per_device": per, "leaves_split": split}


def phase_sharded_train(cfg, *, steps: int = 5, batch: int = 8,
                        seq: int = 1024) -> dict:
    """The training launcher's mesh path: (data 2, model 2) over 4 devices
    against a one-device mesh, same config, seed and batches."""
    devs = jax.devices()
    _check(len(devs) >= 4, f"sharded train: needs 4 devices, found {len(devs)}")
    auto = (jax.sharding.AxisType.Auto,) * 2
    one = jax.sharding.Mesh(np.asarray(devs[:1]).reshape(1, 1),
                            ("data", "model"), axis_types=auto)
    four = make_host_mesh(model_parallel=2)
    _check(dict(four.shape) == {"data": 2, "model": 2},
           f"sharded train: mesh {dict(four.shape)}, want data 2 x model 2")
    ref = train_run(cfg, one, steps=steps, batch=batch, seq=seq)
    run = train_run(cfg, four, steps=steps, batch=batch, seq=seq)
    spread = _param_spread(run["params"])
    held = [b for b in spread["bytes_per_device"].values() if b > 0]
    _check(len(held) == 4 and spread["leaves_split"] > 0,
           f"sharded train: parameters are not spread over 4 devices: {spread}")
    rel = max(abs(x - y) / abs(y) for x, y in zip(run["losses"], ref["losses"]))
    _check(rel <= SHARDED_LOSS_RTOL,
           f"sharded train: losses {run['losses']} vs one device "
           f"{ref['losses']} (max rel diff {rel:.3g} > {SHARDED_LOSS_RTOL})")
    body = {"mode": cfg.numerics.mode, "mesh": dict(four.shape),
            "losses": run["losses"], "one_device_losses": ref["losses"],
            "max_rel_diff": rel, "compile_s": run["compile_s"],
            "step_s": run["step_s"], "one_device_step_s": ref["step_s"],
            **spread, "peak": _peak_gib()}
    _say("sharded_train", body)
    return body


# ---------------------------------------------------------------- serve
def phase_serve(cfg, *, slots: int = 4, requests: int = 8,
                prompt_len: int = 128, gen: int = 32) -> dict:
    rng = np.random.default_rng(SEED)
    prompts = [tuple(int(t) for t in rng.integers(0, cfg.vocab, prompt_len))
               for _ in range(requests)]
    out = {}
    with jax.set_mesh(make_host_mesh()):
        for nm in (AMRNumerics("exact"),
                   AMRNumerics("amr_kernel", border=BORDER, rank=RANK)):
            _progress(f"serve {nm.mode}")
            c = dataclasses.replace(cfg, numerics=nm)
            params = init_params(c, root_key(SEED))
            busy = ServeEngine(c, params, n_slots=slots, capacity=prompt_len + gen)
            for p in prompts:
                busy.submit(Request(prompt=p, max_new_tokens=gen))
            t0 = time.perf_counter()
            done = busy.run()
            wall = time.perf_counter() - t0
            _check(len(done) == requests
                   and all(len(x.tokens) == gen for x in done),
                   f"serve: {nm.mode}: {len(done)} of {requests} completions")
            alone = ServeEngine(c, params, n_slots=slots, capacity=prompt_len + gen)
            alone.submit(Request(prompt=prompts[-1], max_new_tokens=gen))
            solo = alone.run()[0]
            _check(solo.tokens == done[-1].tokens,
                   f"serve: {nm.mode}: request decoded in the busy engine "
                   f"{done[-1].tokens} != served alone {solo.tokens}")
            out[nm.mode] = {
                "wall_s_with_compile": wall,
                "decode_tok_s": (busy.counters.decode_tokens
                                 / max(busy.counters.decode_seconds, 1e-9)),
                "admit_s": busy.counters.admit_seconds,
                "tokens_last": list(done[-1].tokens[:8]), "solo_match": True,
                "peak": _peak_gib()}
    _say("serve", {"slots": slots, "requests": requests,
                   "prompt_len": prompt_len, "gen": gen, **out})
    return out


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only sharded training on four chips")
    args = ap.parse_args(argv)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    cfg = get_config(ARCH)
    try:
        device = phase_device(args.chips)
        if args.chips == 4:
            phase_sharded_train(cfg)
        else:
            phase_kernels(cfg)
            phase_train(cfg)
            phase_serve(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
