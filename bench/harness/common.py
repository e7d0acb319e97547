"""What every driver shares: the device, peaks, seeds, weights, statistics."""
from __future__ import annotations

import dataclasses
import sys
import time
from contextlib import contextmanager

import numpy as np

from .spec import BENCH, Cell, load_json

PEAKS = BENCH / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices JAX sees; raises NoChip
    unless they are TPUs and at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform!r} ({d.device_kind}, "
                     f"{len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; unknown kinds are an error."""
    table = load_json(PEAKS)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(chips: int) -> int | None:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    got = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in jax.devices()[:chips]]
    got = [g for g in got if g is not None]
    return max(got) if got else None


def prng_key(seed: int, stream: int):
    """A JAX key for ``(seed, stream)``; any non-negative seed, however large."""
    import jax

    words = np.random.default_rng((seed, stream)).integers(0, 2**32, 2, dtype=np.uint64)
    return jax.random.wrap_key_data(words.astype(np.uint32), impl="threefry2x32")


def program_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell: sizes from the cell's
    configuration file, numerics from its workload file."""
    from repro.configs.base import ModelConfig
    from repro.numerics import AMRNumerics

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cell.config.items() if k in fields}
    return ModelConfig(**kw, numerics=AMRNumerics(**cell.numerics))


def make_weights(config: dict, seed: int):
    """Random weights in the program's parameter layout, made on the device
    in one jitted call from ``seed``: normals scaled by fan-in in the
    model's dtype, RMSNorm scales zero (the program's own init scheme)."""
    import jax
    import jax.numpy as jnp

    c = config
    d, hd, L = c["d_model"], c["head_dim"], c["n_layers"]
    dt = jnp.dtype(c["dtype"])
    shapes = {
        "attn": {"wq": (d, c["n_heads"] * hd), "wk": (d, c["n_kv_heads"] * hd),
                 "wv": (d, c["n_kv_heads"] * hd), "wo": (c["n_heads"] * hd, d)},
        "mlp": {"w_gate": (d, c["d_ff"]), "w_up": (d, c["d_ff"]),
                "w_down": (c["d_ff"], d)},
    }

    def build(key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * shape[-2] ** -0.5).astype(dt)

        layer = {grp: {n: normal((L, *s)) for n, s in ws.items()}
                 for grp, ws in shapes.items()}
        layer["ln1"] = jnp.zeros((L, d), jnp.float32)
        layer["ln2"] = jnp.zeros((L, d), jnp.float32)
        p = {"embed": (jax.random.normal(next(keys), (c["vocab"], d)) * d ** -0.5).astype(dt),
             "final_norm": jnp.zeros((d,), jnp.float32), "layers": (layer,)}
        if not c["tie_embeddings"]:
            p["lm_head"] = (jax.random.normal(next(keys), (c["vocab"], d))
                            * d ** -0.5).astype(dt)
        return p

    return jax.jit(build)(prng_key(seed, 0))


class CompileCounter:
    """Counts JAX traces and compilations while ``armed``."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self._EVENTS:
            self.count += 1


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of the
    values at or below it."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(int(np.ceil(p / 100.0 * len(v))) - 1, 0)
    return float(v[k])


class Tracer:
    """The profiler over the measured window (``dir`` None: off)."""

    def __init__(self, dir):
        self.dir = dir
        self.on = dir is not None
        self._running = False

    def start(self) -> None:
        if not self.on:
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._running = True

    def stop(self) -> None:
        if self._running:
            import jax

            jax.profiler.stop_trace()
            self._running = False


@contextmanager
def span(name: str, tracer: Tracer):
    """A harness span in the profiler's trace (only while tracing)."""
    if not tracer.on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def log(msg: str) -> None:
    print(f"bench: {time.strftime('%H:%M:%S')} {msg}", file=sys.stderr, flush=True)
