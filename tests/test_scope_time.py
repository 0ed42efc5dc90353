"""The benchmark's join of device time with the program's scopes
(``benchmarks/chip/scope_time.py`` and the five ``*_ms_per_step``
readers): on a made-up trace, on the small recorded TPU traces, and on
a made-up run."""
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
DATA = CHIP / "tests" / "data"
sys.path.append(str(CHIP))

import harness  # noqa: E402
import scope_time  # noqa: E402
import xplane  # noqa: E402

METRICS = [*scope_time.SCOPE_METRICS, scope_time.UNSCOPED_METRIC]


def plane(name, **lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=a, duration_ns=d)
                            for n, a, d in evs])
        for ln, evs in lines.items()])


def made_up():
    """Window 0-1000 ns. Module ``jit_step`` runs 100-500 (a while.1 of
    100-400 holding fusion.2 and fusion.3, then copy.4); module
    ``jit_copy`` runs 600-1100 with its own fusion.2, cut at 1000."""
    host = plane("/host:CPU", main=[(xplane.WINDOW, 0, 1000)])
    dev = plane("/device:TPU:0", **{
        "XLA Ops": [("%while.1 = (s32[]) while(%t)", 100, 300),
                    ("%fusion.2 = f32[8] fusion(%a)", 120, 100),
                    ("%fusion.3 = f32[8] fusion(%b)", 250, 100),
                    ("%copy.4 = f32[8] copy(%c)", 420, 60),
                    ("%fusion.2 = f32[8] fusion(%a)", 700, 400)],
        "XLA Modules": [("jit_step(123)", 100, 400),
                        ("jit_copy(456)", 600, 500)]})
    return [host, dev]


SCOPES = {"jit_step/while.1": "container", "jit_step/fusion.2": "kv_cache",
          "jit_step/fusion.3": "attention", "jit_step/copy.4": None}


def test_op_seconds_keyed_by_module_and_clipped():
    ops = scope_time.op_seconds(made_up())
    assert ops == pytest.approx({
        "jit_step/while.1": 300e-9, "jit_step/fusion.2": 100e-9,
        "jit_step/fusion.3": 100e-9, "jit_step/copy.4": 60e-9,
        "jit_copy/fusion.2": 300e-9})


def test_op_seconds_reads_nothing_without_window_or_device():
    planes = made_up()
    assert scope_time.op_seconds(planes[1:]) is None
    assert scope_time.op_seconds(planes[:1]) is None


def test_scope_seconds_leave_containers_out():
    per = scope_time.scope_seconds(scope_time.op_seconds(made_up()), SCOPES)
    assert per == pytest.approx({"kv_cache": 100e-9, "attention": 100e-9,
                                 None: 360e-9})


def _run(device, steps=4):
    return harness.Run({}, {"steps": steps}, [], device, {})


def test_readers_add_up_to_busy_time_per_step(monkeypatch):
    monkeypatch.setattr(scope_time, "program_scopes", lambda: SCOPES)
    planes = made_up()
    dev = xplane.reduce_profile(planes)
    dev["op_seconds"] = scope_time.op_seconds(planes)
    run = _run(dev)
    got = {m: harness.reader(m).read(run) for m in METRICS}
    assert got["kv_cache_ms_per_step"] == pytest.approx(1e3 * 100e-9 / 4)
    assert got["attention_ms_per_step"] == pytest.approx(1e3 * 100e-9 / 4)
    assert got["pim_quantize_ms_per_step"] == 0
    assert got["pim_matmul_ms_per_step"] == 0
    assert sum(got.values()) == pytest.approx(1e3 * dev["busy_s"] / 4)


def test_readers_read_nothing_without_trace_map_or_steps(monkeypatch):
    monkeypatch.setattr(scope_time, "program_scopes", lambda: SCOPES)
    planes = made_up()
    dev = xplane.reduce_profile(planes)
    for run in (_run(None), _run(dev),           # no trace; no op_seconds
                _run(dict(dev, op_seconds=scope_time.op_seconds(planes)),
                     steps=0)):
        assert all(harness.reader(m).read(run) is None for m in METRICS)
    monkeypatch.setattr(scope_time, "program_scopes", lambda: {})
    run = _run(dict(dev, op_seconds=scope_time.op_seconds(planes)))
    assert all(harness.reader(m).read(run) is None for m in METRICS)


def test_program_without_scopes_reads_an_empty_map(monkeypatch):
    from repro import obs
    monkeypatch.delattr(obs, "device_scopes")
    assert scope_time.program_scopes() == {}


@pytest.mark.skipif(not (DATA / "tpu_v5e.xplane.pb").exists(),
                    reason="recorded trace not present")
def test_reduce_dir_adds_op_seconds_and_keeps_the_rest(tmp_path):
    """On the recorded trace the reduction is xplane's, key for key, plus
    ``op_seconds`` keyed by the one module that ran."""
    shutil.copy(DATA / "tpu_v5e.xplane.pb", tmp_path / "t.xplane.pb")
    spans = json.loads((DATA / "tpu_v5e.spans.json").read_text())
    plain = xplane.reduce_dir(str(tmp_path), spans)
    dev = scope_time.reduce_dir(str(tmp_path), spans)
    ops = dev.pop("op_seconds")
    assert dev == plain
    assert ops and all(k.startswith("jit__lambda/") for k in ops)
    assert sum(ops.values()) == pytest.approx(plain["busy_s"])


SCOPED = DATA / "tpu_v5e_scoped.xplane.pb"


@pytest.mark.skipif(not SCOPED.exists(), reason="recorded trace not present")
def test_recorded_scoped_trace_joins_with_the_program_map():
    """``record_scoped_trace.py``: a scan with a ``kv_cache`` read and an
    ``attention`` product, on a TPU v5e. Every op of the program in the
    window is in its map; both scopes hold time; the body's ops are not
    counted again with their ``while``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(SCOPED))
    scopes = json.loads((DATA / "tpu_v5e_scoped.scopes.json").read_text())
    dev = xplane.reduce_profile(data.planes)
    ops = scope_time.op_seconds(data.planes)
    module = next(iter(scopes)).split("/")[0]
    mine = {k: v for k, v in ops.items() if k.startswith(module + "/")}
    assert mine and all(k in scopes for k in mine)
    per = scope_time.scope_seconds(ops, scopes)
    assert per.get("kv_cache", 0) > 0 and per.get("attention", 0) > 0
    containers = sum(v for k, v in mine.items()
                     if scopes[k] == scope_time.CONTAINER)
    assert containers > 0
    assert sum(per.values()) <= dev["busy_s"] * (1 + 1e-9)
    assert sum(ops.values()) > dev["busy_s"]
