#!/usr/bin/env python3
"""Record the small scoped TPU trace that ``test_scope_time.py`` joins.

    python benchmarks/chip/tests/record_scoped_trace.py OUT_DIR

On the chip: a jitted ``lax.scan`` whose body copies one layer of a
stacked state into another in the ``kv_cache`` scope and multiplies in
the ``attention`` scope, with sums outside every scope, registered with
``repro.obs`` and called three times inside the harness's window
annotation. Writes ``OUT_DIR/scoped.xplane.pb`` and
``OUT_DIR/scoped.scopes.json`` (the program's ``device_scopes()`` map),
committed as ``data/tpu_v5e_scoped.*``.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro import obs

    import xplane

    def layers(stack, w):
        def body(carry, i):
            x, seen = carry
            with obs.scope(obs.KV_CACHE):
                s = jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
                seen = jax.lax.dynamic_update_index_in_dim(seen, s, i, 0)
            with obs.scope(obs.ATTENTION):
                x = jnp.tanh(x @ w + s)
            return (x, seen), None
        (x, seen), _ = jax.lax.scan(
            body, (jnp.zeros_like(w), jnp.zeros_like(stack)), jnp.arange(4))
        return x.sum() + seen.sum()

    stack = jnp.ones((4, 512, 512), jnp.float32)
    w = jnp.full((512, 512), 1e-3, jnp.float32)
    f = jax.jit(layers)
    obs.register_program(f, stack, w)
    f(stack, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            for _ in range(3):
                f(stack, w).block_until_ready()
        jax.profiler.stop_trace()
        pb = next(Path(d).rglob("*.xplane.pb"))
        Path(out).mkdir(parents=True, exist_ok=True)
        shutil.copy(pb, Path(out) / "scoped.xplane.pb")
    (Path(out) / "scoped.scopes.json").write_text(
        json.dumps(obs.device_scopes(), indent=0, sort_keys=True))


if __name__ == "__main__":
    main(sys.argv[1])
