#!/usr/bin/env python3
"""Record the small TPU trace that ``test_xplane.py`` reduces.

    python benchmarks/chip/tests/record_trace.py OUT_DIR

On the chip: three calls of a small jitted program inside the
harness's window annotation, with a program span around each call, and
10 ms of host sleep between calls. Writes ``OUT_DIR/trace.xplane.pb``
and ``OUT_DIR/spans.json`` (the program's spans).
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro import obs

    import xplane
    x = jnp.ones((512, 512), jnp.float32)
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        obs.enable()
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            obs.instant(xplane.WINDOW)
            for i in range(3):
                with obs.span("test.call", i=i):
                    f(x).block_until_ready()
                with obs.span("test.sleep", i=i):
                    time.sleep(0.01)
        jax.profiler.stop_trace()
        obs.disable()
        pb = next(Path(d).rglob("*.xplane.pb"))
        Path(out).mkdir(parents=True, exist_ok=True)
        shutil.copy(pb, Path(out) / "trace.xplane.pb")
    spans = obs.get_tracer().trace_dict()["traceEvents"]
    (Path(out) / "spans.json").write_text(json.dumps(spans))


if __name__ == "__main__":
    main(sys.argv[1])
