"""The controls, at a size a test run holds on the CPU: a whole run with
a control put in the program's place in the check (the plain reference
one precision step below what the configuration states) comes out not
correct where the program's own run comes out correct. ``control.py``
reads the same on the chip at the cells' own sizes."""
import time

import harness

SEED = 2**31 + 1


def run(cell, control=None):
    wl, config, traffic = cell
    return harness.run_cell(wl["name"], SEED, 0.5, False,
                            t_start=time.perf_counter(),
                            cell_override=(wl, config, traffic),
                            require_chip=False, control=control)


def test_decode_control_pim_bits_4_fails(decode_cell):
    """The reference with its PIM linears at 4 bits, read at the stated
    8 bits, lies past the limits where the program lies within them."""
    assert run(decode_cell)["correct"]
    r = run(decode_cell, "pim_bits_4")
    assert r["correct"] is False, r["compared"]


def test_decode_control_matmul_default_fails(decode_cell):
    """The reference with its float products at one bfloat16 pass."""
    assert run(decode_cell)["correct"]
    r = run(decode_cell, "matmul_default")
    assert r["correct"] is False, r["compared"]
