"""Training cells: the program's jitted train step, timed over a window,
and its first steps compared with the reference.

Set-up builds one object, the compiled step with its donated state, and
drives it through its first ``CHECKED_STEPS`` steps on the run's own
batches (the window's call and feed); the window goes on from that state.
Those steps give the numbers the check compares:

* ``loss_gap``: each checked step's loss against the reference's,
  relative, the worst step;
* ``grad_gap``: the first gradient as the optimizer took it (read back from
  AdamW's first moment after one step), each leaf's norm against the
  reference's, relative to that leaf's reference norm, the worst leaf;
* ``grad_dir_gap``: 1 - cosine between that gradient and the reference's,
  the worst leaf (the global-norm clip rescales every gradient, so its
  leaves' norms barely see a gradient taken over part of the batch; its
  direction does);
* ``grad_dir_median``: the same on the median leaf;
* ``delta_gap``: the same as ``grad_gap`` for each leaf's change of the
  float32 master weights after the checked steps;
* ``delta_median``: that change's relative norm gap on the median leaf.

Under AMR numerics the bfloat16 rounding of the seam's inputs moves many
int8 operands by one step, and AMR-MUL's error differs from one operand
to the next, so the worst leaf's numbers are set by that rounding (the
reference reads the same against itself with its inputs so rounded).
Every number leaves out
leaves whose reference gradient is under a thousandth of the median
leaf's (they move by round-off alone).  Which numbers are held to a limit
is the cell's ``limits``; the others are printed beside them.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from . import common, flops
from .common import span
from .spec import Cell
from .traffic import train_batch

CHECKED_STEPS = 3
TINY_GRAD = 1e-3


def hyper(cell: Cell) -> dict:
    t = cell.traffic
    return {"peak_lr": t["peak_lr"], "warmup": t["warmup"], "total_steps": t["total_steps"]}


def build_step(cfg, hp: dict):
    """The timed path: the program's train step, jitted, state donated."""
    import jax

    from repro.train.steps import make_train_step

    return jax.jit(make_train_step(cfg, **hp), donate_argnums=(0,))


def batch(cell: Cell, seed: int, index: int) -> dict:
    t = cell.traffic
    return train_batch(seed, index, cell.config["vocab"], t["batch"], t["seq"])


def _delta_norms(master, init) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(lambda m, p: jnp.stack(
        [jnp.linalg.norm((a - b.astype(jnp.float32)).reshape(-1))
         for a, b in zip(jax.tree.leaves(m), jax.tree.leaves(p))]))(master, init))


def run(cell: Cell, seed: int, seconds: float, tracer, counter) -> dict:
    """Set-up, window and the program's side of the check."""
    import jax
    import jax.numpy as jnp

    from repro.optim import adamw_init
    from repro.optim.adamw import AdamWState
    from repro.train.steps import TrainState

    from reference.model import B1

    cfg = common.program_config(cell)
    step = build_step(cfg, hyper(cell))
    params = common.make_weights(cell.config, seed)
    state = TrainState(params, adamw_init(params), jnp.zeros((), jnp.int32))
    del params
    losses = []
    for i in range(CHECKED_STEPS):
        b = {k: jnp.asarray(v) for k, v in batch(cell, seed, i).items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
        if i == 0:
            opt: AdamWState = state.opt
            grads = [np.asarray(x) / (1 - B1) for x in jax.tree.leaves(opt.mu)]
    delta_norms = _delta_norms(state.opt.master, common.make_weights(cell.config, seed))
    common.log(f"set-up steps done, losses {losses}")

    ends, step_losses = [], []
    tracer.start()
    counter.armed = True
    t0 = time.monotonic()
    with span("window", tracer):
        i = CHECKED_STEPS
        while time.monotonic() - t0 < seconds:
            with span("data", tracer):
                b = {k: jnp.asarray(v) for k, v in batch(cell, seed, i).items()}
            with span("train_step", tracer):
                state, m = step(state, b)
                step_losses.append(float(m["loss"]))     # blocks on the step
            ends.append(time.monotonic())
            i += 1
    counter.armed = False
    tracer.stop()
    t = cell.traffic
    tokens = len(ends) * t["batch"] * t["seq"]
    elapsed = ends[-1] - t0
    steps_s = np.diff([t0, *ends])
    common.log(f"window: {len(ends)} steps, median {np.median(steps_s):.4f} s, "
               f"slowest {steps_s.max():.4f} s (step {int(steps_s.argmax())})")
    out = {
        "t_window": t0,
        "attempted": len(ends),
        "failed": sum(not np.isfinite(x) for x in step_losses),
        "e2e": {"train_tokens_per_s": tokens / elapsed},
        "counters": {"window_s": elapsed, "steps": len(ends), "tokens": tokens,
                     "flops_per_token": flops.train_flops_per_token(cell.config, t["seq"])},
        "memory_peak_bytes": common.memory_peak_bytes(cell.chips),
        "program": {"losses": losses, "grads": grads, "delta_norms": delta_norms},
    }
    del state, step, m, b
    gc.collect()
    jax.clear_caches()
    return out


# ----------------------------------------------------------- reference
def reference_readings(cell: Cell, seed: int, mode=None, rows: int | None = None) -> dict:
    """The reference's losses, first clipped gradient and master-weight
    change over the checked steps, on the same weights and batches.

    ``mode`` (default: the cell's) and ``rows`` (default: the whole batch;
    fewer leaves rows out and takes the mean over the rest) let the same
    procedure stand in the program's place as a control or a fault."""
    import jax
    import jax.numpy as jnp

    from reference import model as ref

    sz = ref.Sizes.of(cell.config)
    mode = mode or ref.Mode.of(cell.numerics)
    hp = hyper(cell)
    weights = common.make_weights(cell.config, seed)
    master = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    init = master
    del weights
    mu = jax.tree.map(jnp.zeros_like, master)
    nu = jax.tree.map(jnp.zeros_like, master)
    grad_fn = jax.jit(jax.value_and_grad(ref.seq_loss), static_argnums=(3, 4))
    add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g))
    update = jax.jit(ref.adamw, static_argnums=(4,))
    losses = []
    for i in range(CHECKED_STEPS):
        b = batch(cell, seed, i)
        n = rows or b["tokens"].shape[0]
        total, acc = 0.0, None
        for r in range(n):
            loss, g = grad_fn(master, jnp.asarray(b["tokens"][r]),
                              jnp.asarray(b["targets"][r]), sz, mode)
            total += float(loss)
            acc = g if acc is None else add(acc, g)
        grads = ref.clip(jax.tree.map(lambda x: x / n, acc))
        if i == 0:
            first = [np.asarray(x) for x in jax.tree.leaves(grads)]
        losses.append(total / n)
        lr = float(ref.lr_at(i, peak_lr=hp["peak_lr"], warmup=hp["warmup"],
                             total=hp["total_steps"]))
        master, mu, nu = update(master, mu, nu, grads, i + 1, lr)
    return {"losses": losses, "grads": first,
            "delta_norms": _delta_norms(master, init)}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the check compares (see the module docstring)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    norm = lambda gs: np.array([np.linalg.norm(g.reshape(-1).astype(np.float64)) for g in gs])
    g_prog, g_ref = norm(prog["grads"]), norm(ref["grads"])
    moved = g_ref >= TINY_GRAD * np.median(g_ref)
    cos = np.array([float(np.dot(a.reshape(-1).astype(np.float64),
                                 b.reshape(-1).astype(np.float64)) / (na * nb))
                    if na > 0 else 0.0
                    for a, b, na, nb, m in zip(prog["grads"], ref["grads"], g_prog, g_ref, moved)
                    if m])
    g_rel = np.abs(g_prog - g_ref)[moved] / g_ref[moved]
    d_ref = ref["delta_norms"][moved]
    d_rel = np.abs(prog["delta_norms"][moved] - d_ref) / d_ref
    return {"loss_gap": loss_gap, "grad_gap": float(np.max(g_rel)),
            "grad_dir_gap": float(1.0 - np.min(cos)),
            "grad_dir_median": float(1.0 - np.median(cos)),
            "delta_gap": float(np.max(d_rel)), "delta_median": float(np.median(d_rel))}
