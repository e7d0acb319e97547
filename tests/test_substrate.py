"""Substrate tests: checkpointing, data pipeline, fault tolerance, optimizers."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager, restore_tree, save_tree
from repro.ckpt.checkpoint import latest_step
from repro.data import SyntheticLM
from repro.optim import adafactor_init, adafactor_update, adamw_init, adamw_update
from repro.runtime import FaultTolerantLoop, StragglerMonitor


class TestCheckpoint:
    def _tree(self, k=0):
        return {"a": jnp.arange(6.0).reshape(2, 3) + k,
                "nested": {"b": jnp.ones((4,), jnp.int32) * k}}

    def test_roundtrip(self, tmp_path):
        t = self._tree(3)
        path = save_tree(tmp_path, t, step=7)
        back = restore_tree(path, jax.eval_shape(lambda: t))
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_atomic_no_partial(self, tmp_path):
        save_tree(tmp_path, self._tree(), step=1)
        assert not list(tmp_path.glob(".tmp-*"))
        assert latest_step(tmp_path) == 1

    def test_manager_retention_and_latest(self, tmp_path):
        m = CheckpointManager(tmp_path, keep=2)
        for s in (0, 10, 20, 30):
            m.save(self._tree(s), s)
        steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
        assert steps == [0, 20, 30]  # step 0 always kept
        got, step = m.restore_latest(jax.eval_shape(lambda: self._tree()))
        assert step == 30
        assert float(np.asarray(got["a"])[0, 0]) == 30.0

    def test_async_save(self, tmp_path):
        m = CheckpointManager(tmp_path)
        m.save_async(self._tree(5), 5)
        m.wait()
        assert latest_step(tmp_path) == 5


class TestData:
    def test_deterministic_and_resumable(self):
        d = SyntheticLM(vocab=97, seq_len=16, batch=4, seed=3)
        b1 = d.batch_at(12)
        b2 = d.batch_at(12)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert b1["tokens"].shape == (4, 16)

    def test_targets_shifted(self):
        d = SyntheticLM(vocab=97, seq_len=16, batch=2)
        b = d.batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])

    def test_learnable_structure(self):
        """Next token is an affine function of current most of the time."""
        d = SyntheticLM(vocab=97, seq_len=64, batch=8, seed=0, noise=0.05)
        b = d.batch_at(0)
        a = 6364136223846793005 % 97 or 5
        c = 1442695040888963407 % 97 or 7
        pred = (a * b["tokens"].astype(np.int64) + c) % 97
        agree = (pred == b["targets"]).mean()
        assert agree > 0.85


class TestOptim:
    def _quad_problem(self, update, init):
        w = {"w": jnp.array([3.0, -2.0])}
        state = init(w)
        for _ in range(200):
            g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(w)
            w, state = update(g, state, w, 0.05, weight_decay=0.0)
        return float(jnp.abs(w["w"]).max())

    def test_adamw_converges(self):
        assert self._quad_problem(adamw_update, adamw_init) < 0.05

    def test_adafactor_converges(self):
        assert self._quad_problem(adafactor_update, adafactor_init) < 0.1

    def test_adamw_grad_clip(self):
        w = {"w": jnp.ones((3,))}
        st = adamw_init(w)
        g = {"w": jnp.full((3,), 1e9)}
        w2, _ = adamw_update(g, st, w, 0.1)
        assert np.isfinite(np.asarray(w2["w"], np.float32)).all()


class TestFaultTolerance:
    def test_straggler_monitor(self):
        m = StragglerMonitor(window=20, threshold=2.0)
        for i in range(10):
            m.observe(i, 1.0)
        assert m.observe(10, 5.0) is True
        assert m.observe(11, 1.1) is False
        assert len(m.flagged) == 1

    def test_loop_retries_from_checkpoint(self, tmp_path):
        """A transient step failure restarts from the last checkpoint."""
        calls = {"n": 0}

        def make_state():
            return {"x": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)}

        def step_fn(state, batch):
            calls["n"] += 1
            if calls["n"] == 7:  # injected node failure
                raise RuntimeError("simulated device loss")
            x = state["x"] + batch["v"]
            return {"x": x, "step": state["step"] + 1}, {"loss": x}

        loop = FaultTolerantLoop(
            ckpt_dir=tmp_path, make_state=make_state, step_fn=step_fn,
            batch_at=lambda i: {"v": jnp.asarray(1.0)}, ckpt_every=2,
            max_retries=2)
        res = loop.run(total_steps=10, log=lambda *_: None)
        assert res.steps_done == 10
        assert res.restarts == 1
        assert float(res.final_state["x"]) == 10.0  # deterministic despite retry

    def test_elastic_remesh_hook_called(self, tmp_path):
        seen = {"n": 0}

        def make_state():
            return {"x": jnp.zeros(())}

        def remesh(state):
            seen["n"] += 1
            return state

        loop = FaultTolerantLoop(
            ckpt_dir=tmp_path, make_state=make_state,
            step_fn=lambda s, b: ({"x": s["x"] + 1}, {}),
            batch_at=lambda i: None, ckpt_every=2, remesh=remesh)
        loop.run(total_steps=4, log=lambda *_: None)
        # second run restores from ckpt -> remesh must fire (elastic restart)
        loop2 = FaultTolerantLoop(
            ckpt_dir=tmp_path, make_state=make_state,
            step_fn=lambda s, b: ({"x": s["x"] + 1}, {}),
            batch_at=lambda i: None, ckpt_every=2, remesh=remesh)
        res = loop2.run(total_steps=6, log=lambda *_: None)
        assert seen["n"] >= 1
        assert res.steps_done == 6


class TestCompressedCollective:
    def test_quant_psum_single_axis(self):
        """int8-compressed psum matches exact within quantization error."""
        from repro.parallel.collectives import compressed_psum_tree
        mesh = jax.make_mesh((1,), ("dp",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from jax.sharding import PartitionSpec as P

        g = {"w": jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)}
        f = jax.shard_map(lambda t: compressed_psum_tree(t, "dp"), mesh=mesh,
                          in_specs=(jax.tree.map(lambda _: P(), g),),
                          out_specs=jax.tree.map(lambda _: P(), g),
                          check_vma=False)
        out = f(g)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                                   atol=2.0 / 127)
