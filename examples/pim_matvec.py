"""Section VI end to end: full-precision fixed-point matrix-vector
multiplication on the simulated crossbar (through the engine), a
PIM-mode linear layer (repro.pim), and the LM-scale PIM plan.

    PYTHONPATH=src python examples/pim_matvec.py
"""
import numpy as np

from repro import pim
from repro.configs import get_config
from repro.core.matvec import (floatpim_matvec_latency,
                               matvec_latency_formula)
from repro.engine import get_engine
from repro.pim import gemms_from_config, plan_model

eng = get_engine()

# 1. the paper's Table III configuration, analytically:
n, N = 8, 32
print(f"Table III (n={n}, N={N}): FloatPIM {floatpim_matvec_latency(n, N)} "
      f"cycles vs MultPIM {matvec_latency_formula(n, N)} cycles "
      f"({floatpim_matvec_latency(n, N)/matvec_latency_formula(n, N):.1f}x)")

# 2. executable at reduced width: every matrix row is one crossbar row.
#    One engine call — the MAC schedule compiles once into the shared
#    cache (and onto disk), the 8 rows ride the SIMD batch axis.
A = np.random.default_rng(0).integers(0, 60, (8, 6))
x = np.random.default_rng(1).integers(0, 60, 6)
res, cycles = eng.matvec(A, x, 8)
ok = all(int(r) == int(w) for r, w in zip(res, A.astype(object) @ x))
print(f"crossbar matvec 8x6 @ 8-bit: {cycles} cycles, bit-exact={ok}")

# 3. the MAC's fixed-point semantics as a neural linear layer (what the
#    serve path runs for PIM-mode LM heads):
import jax.numpy as jnp
xf = jnp.asarray(np.random.default_rng(2).standard_normal((4, 64)),
                 jnp.float32)
wf = jnp.asarray(np.random.default_rng(3).standard_normal((64, 16)),
                 jnp.float32)
y = pim.linear(xf, wf, 8)
yref = np.asarray(xf @ wf)
err = float(np.max(np.abs(np.asarray(y) - yref)))
print(f"PIM-mode linear 4x64x16 @ 8-bit: max |err| vs float = {err:.3f}")
print(f"engine cache after matvec: {eng.stats()}")

# 4. what a PIM accelerator would do to a real LM layer stack:
cfg = get_config("deepseek-7b")
plan = plan_model(gemms_from_config(cfg, batch_tokens=1), n_bits=8)
print()
print(plan.summary())
