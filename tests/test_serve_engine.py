"""Continuous-batching serve engine: slot allocator properties, FIFO
fairness, prefill->slot handoff parity, and the core invariant — batched
slot-decode is bit-identical to decoding each request alone, across the
numerics modes and mixed request lengths."""
import json

import numpy as np
import pytest

import jax

from _hyp import given, settings, st
from _trace_utils import assert_single_trace
from repro.configs.base import ModelConfig
from repro.models import decode_step, init_params, prefill_with_cache
from repro.numerics import AMRNumerics
from repro.runtime.fault import Heartbeat, StragglerMonitor
from repro.serve import Request, RequestQueue, ServeEngine, SlotAllocator

CAP = 24
PROMPTS = [(5, 9, 2, 7), (3, 11, 4, 1, 8, 6), (13, 2), (9, 7, 9, 1, 2)]


def tiny_cfg(numerics=None):
    return ModelConfig(
        name="serve-test", family="dense", vocab=61, d_model=32, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
        numerics=numerics or AMRNumerics("exact"))


@pytest.fixture(scope="module")
def exact_setup():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------- allocator
class TestSlotAllocator:
    def test_basic_lifecycle(self):
        al = SlotAllocator(2)
        a, b = al.allocate(), al.allocate()
        assert {a, b} == {0, 1}
        assert al.allocate() is None  # full
        al.free(a)
        assert al.allocate() == a  # freed capacity is reusable

    def test_double_free_rejected(self):
        al = SlotAllocator(2)
        s = al.allocate()
        al.free(s)
        with pytest.raises(ValueError):
            al.free(s)

    def test_free_unallocated_rejected(self):
        with pytest.raises(ValueError):
            SlotAllocator(2).free(0)

    def test_zero_slots_rejected(self):
        with pytest.raises(ValueError):
            SlotAllocator(0)

    @given(st.lists(st.booleans(), max_size=60), st.integers(1, 5))
    @settings(max_examples=50)
    def test_never_double_allocates_and_frees_restore_capacity(self, ops, n):
        al = SlotAllocator(n)
        held = []
        for want_alloc in ops:
            if want_alloc:
                s = al.allocate()
                if len(held) == n:
                    assert s is None  # full allocator must refuse
                else:
                    assert s is not None and s not in held
                    held.append(s)
            elif held:
                al.free(held.pop(0))
            assert al.in_use == set(held)
            assert al.n_free == n - len(held)


# -------------------------------------------------------------------- queue
class TestRequestQueue:
    def test_fifo_order_and_uids(self):
        q = RequestQueue()
        uids = [q.submit(Request(prompt=(1,), max_new_tokens=1))
                for _ in range(5)]
        assert uids == sorted(uids)
        assert [q.pop().uid for _ in range(5)] == uids

    def test_request_validation(self):
        with pytest.raises(ValueError):
            Request(prompt=(), max_new_tokens=1)
        with pytest.raises(ValueError):
            Request(prompt=(1,), max_new_tokens=0)


# ------------------------------------------------------------------- engine
class TestServeEngine:
    def test_capacity_guard_rejects_oversized_request(self, exact_setup):
        cfg, params = exact_setup
        eng = ServeEngine(cfg, params, n_slots=1, capacity=8)
        with pytest.raises(ValueError, match="capacity"):
            eng.submit(Request(prompt=(1, 2, 3, 4), max_new_tokens=5))
        eng.submit(Request(prompt=(1, 2, 3, 4), max_new_tokens=4))  # fits

    def test_prefill_handoff_matches_manual_decode_loop(self, exact_setup):
        """Engine (1 slot) == hand-rolled prefill + scalar-cache decode."""
        cfg, params = exact_setup
        prompt, gen = PROMPTS[1], 5
        logits, cache = prefill_with_cache(
            cfg, params, jax.numpy.asarray(prompt, jax.numpy.int32)[None, :], CAP)
        tok = int(np.argmax(np.asarray(logits[:, -1])[0]))
        want = [tok]
        for _ in range(gen - 1):
            logits, cache = decode_step(
                cfg, params, jax.numpy.asarray([[tok]], jax.numpy.int32), cache)
            tok = int(np.argmax(np.asarray(logits[:, -1])[0]))
            want.append(tok)

        eng = ServeEngine(cfg, params, n_slots=1, capacity=CAP)
        eng.submit(Request(prompt=prompt, max_new_tokens=gen))
        [done] = eng.run()
        assert list(done.tokens) == want

    def test_fifo_admission_fairness(self, exact_setup):
        """With 1 slot, requests are admitted (and finish) in submit order."""
        cfg, params = exact_setup
        eng = ServeEngine(cfg, params, n_slots=1, capacity=CAP)
        uids = [eng.submit(Request(prompt=p, max_new_tokens=2))
                for p in PROMPTS]
        done = eng.run()
        assert [c.uid for c in done] == uids
        admits = sorted((c.t_admit, c.uid) for c in done)
        assert [u for _, u in admits] == uids  # admitted strictly in order

    def test_eviction_frees_slots_for_readmission(self, exact_setup):
        """More requests than slots: finished slots are reused, all complete."""
        cfg, params = exact_setup
        eng = ServeEngine(cfg, params, n_slots=2, capacity=CAP)
        for i, p in enumerate(PROMPTS * 2):
            eng.submit(Request(prompt=p, max_new_tokens=2 + i % 3))
        done = eng.run()
        assert len(done) == len(PROMPTS) * 2
        assert eng.slots.n_free == 2 and not eng.queue
        assert all(c.finish_reason == "length" for c in done)

    def test_eos_finishes_early(self, exact_setup):
        """EOS stops the stream right after its first occurrence, at a
        position past the first token (a token first seen at position 0
        would test only an immediate stop)."""
        cfg, params = exact_setup
        rng = np.random.default_rng(0)
        candidates = list(PROMPTS) + [
            tuple(int(t) for t in rng.integers(0, cfg.vocab, n)) for n in (3, 5, 7, 9) * 4]
        eng = ServeEngine(cfg, params, n_slots=1, capacity=CAP)
        for p in candidates:
            eng.submit(Request(prompt=p, max_new_tokens=8))
        pick = None
        for ref in eng.run():
            later = [k for k, t in enumerate(ref.tokens) if k and t not in ref.tokens[:k]]
            if later:
                pick = (ref, later[0])
                break
        assert pick is not None, (
            "no candidate prompt's reference stream has a token first seen "
            "after position 0; add prompts")
        ref, k = pick
        eng2 = ServeEngine(cfg, params, n_slots=1, capacity=CAP)
        eng2.submit(Request(prompt=ref.prompt, max_new_tokens=8, eos_id=ref.tokens[k]))
        [done] = eng2.run()
        assert done.finish_reason == "eos"
        assert done.tokens == ref.tokens[:k + 1]

    def test_no_recompile_across_admit_evict_patterns(self, exact_setup):
        """The masked decode step traces ONCE no matter which slots are live."""
        cfg, params = exact_setup
        eng = ServeEngine(cfg, params, n_slots=3, capacity=CAP)
        for i, p in enumerate(PROMPTS * 2):  # staggered finishes + readmits
            eng.submit(Request(prompt=p, max_new_tokens=1 + i % 4))
        eng.run()
        assert_single_trace(eng._decode, "masked decode step")

    def test_heartbeat_and_straggler_wiring(self, exact_setup, tmp_path):
        cfg, params = exact_setup
        hb = Heartbeat(tmp_path / "hb.json", interval_s=60.0)
        mon = StragglerMonitor(window=10, threshold=2.5)
        eng = ServeEngine(cfg, params, n_slots=2, capacity=CAP,
                          heartbeat=hb, straggler=mon)
        for p in PROMPTS:
            eng.submit(Request(prompt=p, max_new_tokens=3))
        done = eng.run()
        payload = json.loads((tmp_path / "hb.json").read_text())
        assert payload["completed"] == len(done)
        assert payload["queued"] == 0 and payload["active_slots"] == 0
        assert payload["step"] == eng.counters.decode_steps
        # every decode step was observed by the straggler monitor
        assert len(mon.times) == min(eng.counters.decode_steps, 10)

    def test_counters_count_exactly_and_reset(self, exact_setup):
        """2 slots, 3 requests of 3, 2 and 4 tokens: A and B admitted; step 1
        finishes B; C admitted; step 2 finishes A; steps 3-4 finish C."""
        cfg, params = exact_setup
        eng = ServeEngine(cfg, params, n_slots=2, capacity=CAP)
        prompts, gens = PROMPTS[:3], (3, 2, 4)
        for p, g in zip(prompts, gens):
            eng.submit(Request(prompt=p, max_new_tokens=g))
        eng.run()
        c = eng.counters
        assert c.admitted == 3
        assert c.prefill_tokens == sum(len(p) for p in prompts)
        assert c.prefill_compiles == len({len(p) for p in prompts})
        assert c.decode_tokens == sum(g - 1 for g in gens)  # first tokens come from prefill
        assert c.decode_steps == 4
        assert c.admit_seconds > 0 and c.decode_seconds > 0 and c.queue_wait_seconds >= 0
        assert (eng.decode_seconds, eng.decode_tokens) == (c.decode_seconds, c.decode_tokens)
        st = eng.stats()
        assert st["decode_steps"] == 4 and st["admitted"] == 3
        c.reset()
        assert all(getattr(c, f) == 0 for f in ("decode_steps", "decode_seconds",
                                                "decode_tokens", "admitted",
                                                "admit_seconds", "prefill_tokens",
                                                "prefill_compiles", "queue_wait_seconds"))
        assert eng.decode_seconds == 0 and eng.decode_tokens == 0
        # the same lengths again: the prefill compiles no more
        eng.submit(Request(prompt=PROMPTS[0], max_new_tokens=2))
        eng.run()
        assert (c.admitted, c.prefill_compiles, c.decode_steps, c.decode_tokens) == (1, 0, 1, 1)


# ------------------------------------------------- batched-vs-solo exactness
def _serve_all(cfg, params, n_slots, gens):
    eng = ServeEngine(cfg, params, n_slots=n_slots, capacity=CAP,
                      record_logits=True)
    for p, g in zip(PROMPTS, gens):
        eng.submit(Request(prompt=p, max_new_tokens=g))
    return eng.run()


@pytest.mark.parametrize("numerics", [
    AMRNumerics("exact"),
    AMRNumerics("amr_lut", border=2),
    AMRNumerics("amr_inject", border=2),
    AMRNumerics("amr_kernel", border=2, rank=0),
], ids=lambda nm: nm.mode)
def test_batched_decode_bit_identical_to_solo(numerics):
    """THE serving invariant: a request decoded in a busy engine produces
    the same tokens AND bitwise-identical logits as the same request served
    alone — mixed prompt lengths, staggered finishes, slot reuse."""
    cfg = tiny_cfg(numerics)
    params = init_params(cfg, jax.random.PRNGKey(0))
    gens = [3, 5, 4, 3]
    batched = _serve_all(cfg, params, 3, gens)
    solo = _serve_all(cfg, params, 1, gens)
    assert len(batched) == len(solo) == len(PROMPTS)
    for b, s in zip(batched, solo):
        assert b.tokens == s.tokens
        for lb, ls in zip(b.logits, s.logits):
            assert float(np.max(np.abs(lb - ls))) == 0.0
