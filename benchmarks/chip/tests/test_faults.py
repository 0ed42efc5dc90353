"""A whole run of each cell on the CPU, past the look for a chip, comes
out correct; with its timed path broken underneath in each way the cell
can be broken, it comes out not correct."""
import time

import pytest

import harness


def run(cell, patch=None, seconds=0.3):
    wl, config, traffic = cell
    return harness.run_cell(wl["name"], 2**31 + 11, seconds, False,
                            t_start=time.perf_counter(),
                            cell_override=(wl, config, traffic),
                            require_chip=False, patch=patch)


def _decode_fault(fault):
    def patch(cell):
        step, copy = cell.step, cell.copy
        calls = []

        def broken(params, states, tok, pos):
            calls.append(1)
            if fault == "state unchanged":
                keep = copy(states)
                return step(params, states, tok, pos)[0], keep
            tok, states = step(params, states, tok, pos)
            if fault == "half the batch":
                tok = tok.at[tok.shape[0] // 2:].set(0)
            elif len(calls) == 3:                 # one token altered
                tok = tok.at[1, 0].set((tok[1, 0] + 1)
                                       % cell.config["vocab_size"])
            return tok, states
        cell.step = broken
    return patch


def test_decode_sound_run_is_correct(decode_cell):
    r = run(decode_cell, seconds=2)
    assert r["correct"], r["compared"]
    assert r["compared"]["sessions_disagreeing"]["value"] == 0


@pytest.mark.parametrize("fault", ["state unchanged", "half the batch",
                                   "token altered"])
def test_decode_fault_is_not_correct(decode_cell, fault):
    r = run(decode_cell, _decode_fault(fault), seconds=2)
    assert r["correct"] is False, r["compared"]
