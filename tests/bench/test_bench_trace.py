"""The reduction from trace events to busy time, program time and named
idle gaps, on hand-built events and on a trace recorded on the chip."""
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).with_name("trace_fixture.json")


def _op(plane, start, dur, name="fusion"):
    return {"plane": plane, "line": "XLA Ops", "name": name, "start_ns": start,
            "dur_ns": dur}


def _mod(plane, start, dur, name):
    return {"plane": plane, "line": "XLA Modules", "name": name, "start_ns": start,
            "dur_ns": dur}


def _span(start, dur, name):
    return {"plane": "/host:CPU", "line": "python", "name": name, "start_ns": start,
            "dur_ns": dur}


D = "/device:TPU:0"
EVENTS = [
    _op(D, 0, 100), _op(D, 50, 100),            # overlapping: busy 0..150
    _op(D, 400, 100, name="custom-call.1"),      # busy 400..500
    _op(D, 900, 300),                            # clipped by the window at 1000
    _mod(D, 0, 150, "jit_prefill(1)"), _mod(D, 400, 100, "jit_ivf_scan(2)"),
    _span(100, 400, "bench.search"),             # open over the gap 150..400
    _span(500, 450, "bench.decode"),             # open over the gap 500..900
]


def test_busy_and_idle_by_hand():
    red = trace.reduce(EVENTS, 0, 1000)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((150 + 100 + 100) * 1e-9)
    assert red["n_devices"] == 1


def test_program_and_op_time():
    red = trace.reduce(EVENTS, 0, 1000)
    assert trace.module_seconds(red, "ivf_scan") == pytest.approx(100e-9)
    assert trace.module_seconds(red, "prefill") == pytest.approx(150e-9)
    ops = dict(red["device_ops"])
    assert ops["jit_ivf_scan:custom-call.1"] == pytest.approx(100e-9)
    assert ops["jit_prefill:fusion"] == pytest.approx(200e-9)  # two ops, overlapping
    assert ops["?:fusion"] == pytest.approx(300e-9)  # outside every program


def test_gaps_named_by_the_open_host_span():
    red = trace.reduce(EVENTS, 0, 1000)
    gaps = red["idle_gaps"]
    assert gaps[0] == ["bench.decode", pytest.approx(400e-9)]
    assert gaps[1] == ["bench.search", pytest.approx(250e-9)]
    assert len(gaps) == 2


def test_devices_are_averaged():
    ev = EVENTS + [_op("/device:TPU:1", 0, 1000)]
    red = trace.reduce(ev, 0, 1000)
    assert red["n_devices"] == 2
    assert red["busy_s"] == pytest.approx((350 + 1000) / 2 * 1e-9)


def test_recorded_chip_trace():
    """40 ms recorded on a TPU v5 lite: the reduction gives the numbers it
    gave when the fixture was cut, and they hold together."""
    rec = json.loads(FIXTURE.read_text())
    t0, t1 = rec["marks_ns"]
    red = trace.reduce(rec["events"], t0, t1)
    for key, want in rec["expected"].items():
        got = trace.module_seconds(red, key[7:]) if key.startswith("module:") else red[key]
        assert got == pytest.approx(want, rel=1e-9), key
    assert 0 < red["busy_s"] <= red["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert red["n_devices"] == 1
    # every operation belongs to a program the trace names
    assert all(not name.startswith("?:") for name, _ in red["device_ops"])
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in red["idle_gaps"]) <= idle + 1e-12
