"""The correctness check fails the faults it is there to catch.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, check) at a tiny size on the CPU, with the timed path
broken underneath, and sees ``correct`` come out false; a sound run of the
same cell comes out true.  The tiny cells' limits sit between their sound
and faulted readings; the chip cells' limits are in bench/workloads/.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import _bench_tiny as T
import run
from harness import train

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SEED = 3_000_000_019  # beyond 32 signed bits
LOWRANK = {"mode": "amr_lowrank", "border": 8, "rank": 16}
# tiny exact cell, CPU: sound runs read grad_gap ~1e-3, grad_dir_gap ~2e-4,
# delta_gap ~7e-4; half the batch reads grad_dir_gap ~0.27, the int8
# control ~1.3e-3, a frozen state grad_gap 1
TRAIN_LIMITS = {"grad_gap": 0.01, "grad_dir_gap": 6e-4, "delta_gap": 0.01}
EXACT = {"mode": "exact"}
# tiny amr_lowrank cell, CPU, 4 seeds: sound runs read grad_dir_median
# <= 0.11 and delta_median <= 0.007; half the batch 0.27-0.33 and
# 0.031-0.069; the int4 control grad_dir_median 0.68-0.77
LOWRANK_LIMITS = {"grad_gap": 0.3, "grad_dir_median": 0.2, "delta_gap": 0.3,
                  "delta_median": 0.02}


def _train_cell(numerics=EXACT, limits=TRAIN_LIMITS):
    return T.cell(T.TRAIN, numerics, limits)


def _half_batch(monkeypatch):
    real = train.build_step

    def half(cfg, hp):
        step = real(cfg, hp)
        return lambda s, b: step(s, {k: v[: v.shape[0] // 2] for k, v in b.items()})
    monkeypatch.setattr(train, "build_step", half)


def _run(cell):
    return run.execute(cell, SEED, 0.5, T.NoTrace(), DEVICE)


def test_sound_train_run_is_correct():
    res = _run(_train_cell())
    assert res["correct"], res["checks"]
    assert res["metrics"] == {} and res["device"]["kind"] == "TPU v5 lite"


def test_train_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    from repro.train.steps import loss_fn

    def frozen(cfg, hp):
        return jax.jit(lambda s, b: (s, {"loss": loss_fn(cfg, s.params, b["tokens"],
                                                         b["targets"])[0]}))
    monkeypatch.setattr(train, "build_step", frozen)
    res = _run(_train_cell())
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_train_step_that_leaves_out_half_the_batch_is_caught(monkeypatch):
    _half_batch(monkeypatch)
    assert not _run(_train_cell())["correct"]


def test_sound_lowrank_train_run_is_correct():
    res = _run(_train_cell(LOWRANK, LOWRANK_LIMITS))
    assert res["correct"], res["checks"]


def test_lowrank_train_step_that_leaves_out_half_the_batch_is_caught(monkeypatch):
    _half_batch(monkeypatch)
    res = _run(_train_cell(LOWRANK, LOWRANK_LIMITS))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("numerics,limits", [(EXACT, TRAIN_LIMITS),
                                             (LOWRANK, LOWRANK_LIMITS)],
                         ids=["exact", "lowrank"])
def test_lower_precision_control_fails_the_train_check(numerics, limits):
    from reference import model as ref

    cell = _train_cell(numerics, limits)
    want = train.reference_readings(cell, SEED)
    ctl = train.reference_readings(cell, SEED, mode=ref.Mode.of(numerics).control())
    nums = train.compare(ctl, want)
    assert any(nums[k] > lim for k, lim in limits.items()), nums


@pytest.mark.parametrize("traffic", [T.OPEN], ids=["open"])
def test_serve_token_altered_where_produced_is_caught(monkeypatch, traffic):
    import repro.serve.engine as eng

    real = eng.make_serve_step

    def altered(cfg, **kw):
        step = real(cfg, **kw)

        def bad(params, cache, batch):
            out = step(params, cache, batch)
            return (out[0].at[0].set((out[0][0] + 1) % cfg.vocab),) + out[1:]
        return bad
    monkeypatch.setattr(eng, "make_serve_step", altered)
    cell = T.cell(traffic, {"mode": "exact"}, {"logit_gap": 0.05}, tied=True,
                  check_requests=1000)
    res = _run(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("traffic", [T.OPEN], ids=["open"])
def test_sound_serve_run_is_correct(traffic):
    cell = T.cell(traffic, {"mode": "exact"}, {"logit_gap": 0.05}, tied=True,
                  check_requests=1000)
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_reference_in_program_place_for_serving_reads_as_control():
    """The serve control: the tokens a lower-precision reference puts first,
    judged by the reference, read far above a sound run's gap."""
    from harness import serve

    cell = T.cell(T.OPEN, LOWRANK, {"logit_gap": 0.0})
    toks = jnp.arange(40, dtype=jnp.int32) % 256
    served = [(tuple(int(t) for t in toks[:20]), tuple(int(t) for t in toks[20:]))]
    assert max(serve.gaps(cell, SEED, served, control=True)) > 0.5
