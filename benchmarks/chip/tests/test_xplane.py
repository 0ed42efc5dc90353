"""The trace reduction: hand-worked on a made-up trace, and on a small
trace recorded on a TPU v5e (``record_trace.py``)."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import xplane

DATA = Path(__file__).resolve().parent / "data"


def plane(name, **lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=a, duration_ns=d)
                            for n, a, d in evs])
        for ln, evs in lines.items()])


def made_up():
    """Window 0-1000 ns. Device ops 100-300, 250-400 (overlapping),
    600-700, and 900-1100 (cut at the window's end): busy 300 + 100 +
    100 = 500 ns. Idle: 0-100, 400-600, 700-900, each 200 ns or less."""
    host = plane("/host:CPU", main=[
        (xplane.WINDOW, 0, 1000), ("dispatch", 380, 240),
        ("PjitFunction(step)", 690, 250), ("outer", 0, 950)])
    dev = plane("/device:TPU:0", **{
        "XLA Ops": [("%fusion.1 = f32[8] fusion(%a)", 100, 200),
                    ("%copy.2 = f32[8] copy(%b)", 250, 150),
                    ("%fusion.1 = f32[8] fusion(%a)", 600, 100),
                    ("%dot.3 = f32[8] dot(%c)", 900, 200)],
        "XLA Modules": [("jit_step", 100, 1000)]})
    return [host, dev, plane("/device:TPU:0 SparseCore 0")]


def test_made_up_trace(monkeypatch):
    monkeypatch.setattr(xplane, "SHORT_GAP_NS", 50)
    r = xplane.reduce_profile(made_up())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["devices"] == 1
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"fusion.1": 300e-9, "copy.2": 150e-9,
                                 "dot.3": 100e-9})
    # 0-100: only "outer" (950 ns) covers it; 400-600: dispatch and
    # outer, the inner one takes it; 700-900: PjitFunction (250 ns) is
    # inside outer.
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"outer": 100e-9, "dispatch": 200e-9,
                                  "PjitFunction(step)": 200e-9})


def test_program_spans_join_the_host_events(monkeypatch):
    """Program spans placed through the window instant: the instant is
    at ts 5 us on the span clock, the window starts at 0 ns on the
    profiler's, so spans at ts 5.4-5.5 and 5.5-5.6 us split the idle
    400-600 ns between them (inside ``outer``)."""
    spans = [{"ph": "i", "name": xplane.WINDOW, "ts": 5.0},
             {"ph": "X", "name": "exec.marshal", "ts": 5.4, "dur": 0.1},
             {"ph": "X", "name": "backend.pack", "ts": 5.5, "dur": 0.1}]
    monkeypatch.setattr(xplane, "SHORT_GAP_NS", 50)
    planes = made_up()
    planes[0].lines[0].events = [e for e in planes[0].lines[0].events
                                 if e.name != "dispatch"]
    r = xplane.reduce_profile(planes, spans)
    gaps = dict(r["idle_gaps"])
    assert gaps["exec.marshal"] == pytest.approx(100e-9)
    assert gaps["backend.pack"] == pytest.approx(100e-9)


def test_no_window_or_no_device_reads_nothing():
    planes = made_up()
    assert xplane.reduce_profile(planes[1:]) is None
    assert xplane.reduce_profile([planes[0]]) is None


def test_short_gaps_are_summed_under_one_name():
    r = xplane.reduce_profile(made_up())          # every gap < 10 us
    assert dict(r["idle_gaps"]) == pytest.approx(
        {xplane.SHORT_GAP: 500e-9})


@pytest.mark.skipif(not (DATA / "tpu_v5e.xplane.pb").exists(),
                    reason="recorded trace not present")
def test_recorded_tpu_trace():
    """Three calls of a small program with 10 ms of host sleep between
    them: the device is busy a little, idle mostly, and the idle time
    goes to the sleeps."""
    from jax.profiler import ProfileData
    spans = json.loads((DATA / "tpu_v5e.spans.json").read_text())
    pd = ProfileData.from_file(str(DATA / "tpu_v5e.xplane.pb"))
    r = xplane.reduce_profile(pd.planes, spans)
    assert r["devices"] == 1
    assert 0.03 < r["window_s"] < 0.5
    assert 0 < r["busy_s"] < 0.01
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert gaps.get("test.sleep", 0) > 0.025
    idle = r["window_s"] - r["busy_s"]     # the top ten names hold most
    assert 0.9 * idle < sum(gaps.values()) <= idle * (1 + 1e-9)
