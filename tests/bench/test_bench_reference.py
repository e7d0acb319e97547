"""The benchmark's plain reference agrees with the program at reduced size.

Under ``exact`` the program runs in bfloat16 and the reference in float32:
losses agree to 1% and logits to 0.1 (bf16 keeps 8 mantissa bits; two
layers of rounding stay well inside that).  Under ``amr_lowrank`` an AMR
product jumps by up to ~1400 (of 16384) when a quantized operand moves by
one step, so at width 64 the program's bf16 rounding flips some int8
operands and moves logits by O(1); there the logits are held to lie
closer to the reference than those of the int4 control do.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_tiny import ROOT
from harness import common
from harness.spec import Cell
from reference import amr
from reference import model as ref

LOWRANK = {"mode": "amr_lowrank", "border": 8, "rank": 16}
REDUCED = {  # src/repro/configs/*.py reduced(): amr-paper-100m, minitron-8b
    "amr-paper-100m": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                           head_dim=16, d_ff=128, vocab=256),
    "minitron-8b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=128, vocab=256),
}


def _cell(arch: str, numerics: dict) -> Cell:
    config = json.loads((ROOT / f"bench/configs/{arch}.json").read_text())
    config.update(REDUCED[arch])
    return Cell(arch, 1, config, {}, {"numerics": numerics}, (), ())


def test_frozen_amr_table_is_the_programs():
    from repro.core import lut

    np.testing.assert_array_equal(amr.product_table(8), lut.build_int8_lut(8, engine="numpy"))
    u, v = amr.error_factors(8, 16)
    f = lut.lowrank_factor(8, 16, engine="numpy")
    np.testing.assert_allclose(u @ v.T, f.u @ f.v.T, atol=1e-3)


@pytest.mark.parametrize("arch", sorted(REDUCED))
@pytest.mark.parametrize("numerics", [{"mode": "exact"}, LOWRANK], ids=["exact", "lowrank"])
def test_train_step_loss_matches_reference(arch, numerics):
    from repro.optim import adamw_init
    from repro.train.steps import TrainState, make_train_step

    cell = _cell(arch, numerics)
    cfg = common.program_config(cell)
    w = common.make_weights(cell.config, 21)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cell.config["vocab"], (2, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}
    step = jax.jit(make_train_step(cfg))
    _, m = step(TrainState(w, adamw_init(w), jnp.zeros((), jnp.int32)), batch)
    sz, mode = ref.Sizes.of(cell.config), ref.Mode.of(numerics)
    want = np.mean([float(ref.seq_loss(w, batch["tokens"][i], batch["targets"][i], sz, mode))
                    for i in range(2)])
    assert float(m["loss"]) == pytest.approx(want, rel=1e-2)


def _served_logits(cell, w, prompt, n):
    from repro.serve import Request, ServeEngine

    eng = ServeEngine(common.program_config(cell), w, n_slots=2, capacity=64,
                      record_logits=True)
    eng.submit(Request(prompt=prompt, max_new_tokens=n))
    done = eng.run()[0]
    return np.stack(done.logits), done.tokens


@pytest.mark.parametrize("arch", sorted(REDUCED))
def test_prefill_then_decode_logits_match_reference_exact(arch):
    cell = _cell(arch, {"mode": "exact"})
    w = common.make_weights(cell.config, 22)
    prompt = tuple(range(3, 23))
    got, toks = _served_logits(cell, w, prompt, 10)
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    want = np.asarray(ref.logits(w, seq, ref.Sizes.of(cell.config), ref.Mode("exact")))
    np.testing.assert_allclose(got, want[len(prompt) - 1:], atol=0.1)


def test_prefill_then_decode_logits_match_reference_lowrank():
    cell = _cell("amr-paper-100m", LOWRANK)
    w = common.make_weights(cell.config, 23)
    prompt = tuple(range(3, 23))
    got, toks = _served_logits(cell, w, prompt, 10)
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    sz, mode = ref.Sizes.of(cell.config), ref.Mode.of(LOWRANK)
    want = np.asarray(ref.logits(w, seq, sz, mode))[len(prompt) - 1:]
    ctl = np.asarray(ref.logits(w, seq, sz, mode.control()))[len(prompt) - 1:]
    assert np.median(np.abs(got - want)) < 0.5 * np.median(np.abs(ctl - want))


def test_large_vocabulary_head_in_row_blocks_matches_whole(monkeypatch):
    cell = _cell("minitron-8b", {"mode": "exact"})
    w = common.make_weights(cell.config, 24)
    seq = jnp.arange(3, 19, dtype=jnp.int32)
    sz = ref.Sizes.of(cell.config)
    whole = np.asarray(ref.logits(w, seq, sz, ref.Mode("exact")))
    monkeypatch.setattr(ref, "_HEAD_ROWS", 64)     # 4 blocks of the 256 rows
    blocked = np.asarray(ref.logits(w, seq, sz, ref.Mode("exact")))
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)


def test_bf16_rounding_of_seam_inputs_moves_the_reference_as_far_as_the_program():
    """Under amr_lowrank the program's gradient leaves the reference's by
    what rounding the seam's inputs to bfloat16 does to the reference
    itself: the cause of the train check's worst-leaf readings."""
    import _bench_tiny as T
    from harness import train

    cell = T.cell(T.TRAIN, LOWRANK, {})
    prog = train.run(cell, 25, 0.01, T.NoTrace(), T.Counter())["program"]
    want = train.reference_readings(cell, 25)
    rounded = train.reference_readings(cell, 25, mode=ref.Mode.of(LOWRANK).rounded())
    got, own = train.compare(prog, want), train.compare(rounded, want)
    for k in ("grad_dir_gap", "grad_dir_median"):
        assert 0.5 < got[k] / own[k] < 2.0, (k, got[k], own[k])
