"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bits import to_bits
from repro.core.executor import pack_program, run_numpy
from repro.core.multpim import multpim_multiplier
from repro.kernels.ops import crossbar_run, crossbar_run_ref

pytestmark = pytest.mark.kernels


@pytest.mark.parametrize("n,rows,row_block", [
    (4, 37, 64), (8, 128, 128), (8, 300, 256), (16, 64, 64)])
def test_crossbar_kernel_shape_sweep(n, rows, row_block):
    """Pallas crossbar executor == numpy executor across row counts,
    widths and block shapes (incl. non-divisible rows)."""
    prog = multpim_multiplier(n)
    rng = np.random.default_rng(rows)
    a = rng.integers(0, 1 << n, rows)
    b = rng.integers(0, 1 << n, rows)
    inp = {"a": to_bits(a, n), "b": to_bits(b, n)}
    want = run_numpy(prog, inp)["out"]

    packed = pack_program(prog)
    state = np.zeros((rows, packed.init_mask.shape[1]), np.uint8)
    for name, cols in prog.input_map.items():
        state[:, cols] = inp[name]
    got = crossbar_run(jnp.asarray(state), packed, row_block=row_block)
    got = np.asarray(got)[:, prog.output_map["out"]]
    assert (got == want).all()


def test_crossbar_kernel_vs_ref_oracle():
    prog = multpim_multiplier(8)
    packed = pack_program(prog)
    rng = np.random.default_rng(0)
    state = rng.integers(0, 2, (64, packed.init_mask.shape[1]),
                         dtype=np.uint8)
    got = np.asarray(crossbar_run(jnp.asarray(state), packed))
    ref = np.asarray(crossbar_run_ref(jnp.asarray(state), packed))
    assert (got == ref).all()
