"""The readers of the program's own spans (``bench/program_trace.py``): each
on a hand-made recorder, the idle attribution on hand-made trace events,
and the whole tiny cell served with the program's wall channel on."""
import time

import pytest

from bench import harness, manifest, program_trace as pt
from repro.obs.attribution import wall_breakdown
from repro.obs.trace import TraceRecorder, WallSpan
from tiny import make_root

MS = 1_000_000  # ns


def _add(rec, name, t0, t1, parent=None, **args):
    sp = WallSpan(None, name, args)
    sp.t0, sp.t1, sp.sid, sp.tid = t0, t1, next(rec._sids), 1
    sp.parent = -1 if parent is None else parent.sid
    rec.wall_spans.append(sp)
    return sp


@pytest.fixture
def ctx():
    """Two requests and one of a window [1 s, 2 s): request 1 waits 5 ms
    to be admitted, is served 30 ms and waits 15; request 2's stamp lies
    before the window."""
    rec = TraceRecorder()
    s = 1_000 * MS
    _add(rec, "serve.queue", s, s + 2 * MS, rid=1)
    _add(rec, "sched.admit", s + 5 * MS, s + 5 * MS, rid=1)
    cyc = _add(rec, "sched.cycle", s + 5 * MS, s + 40 * MS)
    gen = _add(rec, "sched.gen_substage", s + 10 * MS, s + 30 * MS, cyc,
               rids=[1, 2], n_steps=2, budget_us=2449)
    dec = _add(rec, "engine.decode", s + 10 * MS, s + 14 * MS, gen,
               live=2, slots=16, ctx=900)
    _add(rec, "engine.decode.pull", s + 11 * MS, s + 14 * MS, dec)
    dec2 = _add(rec, "engine.decode", s + 20 * MS, s + 26 * MS, gen,
                live=4, slots=16, ctx=1900)
    _add(rec, "engine.decode.pull", s + 20 * MS, s + 25 * MS, dec2)
    _add(rec, "engine.prefill", s + 15 * MS, s + 19 * MS, gen, tokens=500, width=512)
    ret = _add(rec, "sched.ret_substage", s + 30 * MS, s + 40 * MS, cyc,
               rids=[1], worker=0)
    sub = _add(rec, "ret.substage", s + 30 * MS, s + 40 * MS, ret, worker=0)
    _add(rec, "ret.host_scan", s + 33 * MS, s + 39 * MS, sub, items=4, rows=4000)
    _add(rec, "ret.substage", s + 45 * MS, s + 47 * MS, None, worker=0)
    _add(rec, "serve.done", s + 50 * MS, s + 50 * MS, rid=1)
    _add(rec, "serve.queue", s - 10 * MS, s - 9 * MS, rid=2)
    _add(rec, "sched.admit", s - 8 * MS, s - 8 * MS, rid=2)
    _add(rec, "serve.done", s + 60 * MS, s + 60 * MS, rid=2)
    rec.counters += [
        (s - MS, "ret.scanned", {"device_rows": 100, "host_rows": 900}),
        (s + 40 * MS, "ret.scanned", {"device_rows": 400, "host_rows": 1800}),
        (s + 3_000 * MS, "ret.scanned", {"device_rows": 9000, "host_rows": 9000})]
    return {"program_trace": rec, "window": (1.0, 2.0)}


def test_breakdown_by_hand(ctx):
    rows = wall_breakdown(ctx["program_trace"])
    assert rows[1] == {"ingress_us": 5000.0, "service_us": 30000.0,
                       "stage_wait_us": 15000.0, "latency_us": 50000.0}
    assert rows[2]["ingress_us"] == 2000.0


def test_request_readers_by_hand(ctx):
    assert pt.ingress_wait_ms(ctx) == pytest.approx(5.0)
    assert pt.stage_wait_ms(ctx) == pytest.approx(15.0)


def test_scheduler_readers_by_hand(ctx):
    assert pt.gen_substage_ms(ctx) == pytest.approx(20.0)
    assert pt.gen_budget_ms(ctx) == pytest.approx(2.449)
    # the cycle's 35 ms less its two backend calls (20 + 10) over 1 s
    assert pt.sched_host_share(ctx) == pytest.approx(0.5)


def test_engine_readers_by_hand(ctx):
    assert pt.decode_host_ms(ctx) == pytest.approx((1 + 1) / 2)
    assert pt.slot_occupancy(ctx) == pytest.approx(100 * (2 / 16 + 4 / 16) / 2)
    assert pt.decode_step_ms(ctx) == pytest.approx(5.0)
    assert pt.prefill_ms_per_ktok(ctx) == pytest.approx(4.0 / 0.5)


def test_retrieval_readers_by_hand(ctx):
    # 6 ms of host scan over two sub-stages, the second with none
    assert pt.ret_host_scan_ms(ctx) == pytest.approx(3.0)
    assert pt.ret_substage_ms(ctx) == pytest.approx(6.0)
    # the window's deltas: 300 device rows of 300 + 900
    assert pt.ret_device_row_share(ctx) == pytest.approx(25.0)


def test_readers_find_nothing_in_an_empty_window(ctx):
    ctx["window"] = (5.0, 6.0)
    assert pt.program_metrics(ctx) == {}


def test_idle_attributed_to_innermost_span():
    ev = [{"plane": "/device:TPU:0", "line": "XLA Ops", "name": "op",
           "start_ns": 0.0, "dur_ns": 10e6},
          {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "op",
           "start_ns": 40e6, "dur_ns": 60e6},
          {"plane": "/host:CPU", "line": "python", "name": "repro.sched.cycle",
           "start_ns": 5e6, "dur_ns": 30e6},
          {"plane": "/host:CPU", "line": "python", "name": "repro.engine.decode.pull",
           "start_ns": 20e6, "dur_ns": 10e6},
          {"plane": "/host:CPU", "line": "python", "name": "bench.decode",
           "start_ns": 0.0, "dur_ns": 100e6}]
    by_name, gaps = pt.idle_by_span(ev, 0.0, 100e6)
    # the 30 ms gap [10, 40): 10 ms in the cycle, 10 in the pull, 5 in
    # the cycle again, the last 5 under no span
    assert by_name == pytest.approx({"sched.cycle": 0.015, "engine.decode.pull": 0.01,
                                     "no span": 0.005})
    assert gaps == [("sched.cycle", pytest.approx(0.03))]


def test_tiny_cell_latency_splits_into_its_parts(tmp_path):
    """Served end to end with the channel on: every finished request's
    ingress wait, stage wait and service add up to its wall latency, and
    every span lies inside its parent."""
    root = make_root(tmp_path)
    spec = manifest.resolve_cell(root, manifest.load_manifest(root), "tiny.tiny-mix")
    recs = []
    res = harness.run(root, spec, 2**33 + 5, 2.0, False, time.perf_counter(),
                      fault=lambda st: recs.append(st.server.wall_trace()))
    rec = recs[0]
    rows = wall_breakdown(rec)
    assert len(rows) >= len([r for r in res["ctx"]["requests"] if r["done"] is not None])
    for row in rows.values():
        parts = row["ingress_us"] + row["stage_wait_us"] + row["service_us"]
        assert abs(parts - row["latency_us"]) < 1000.0
        assert min(row["ingress_us"], row["stage_wait_us"], row["service_us"]) >= 0.0
    by_sid = {s.sid: s for s in rec.wall_spans}
    for s in rec.wall_spans:
        if s.parent >= 0:
            p = by_sid[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1, (p.name, s.name)
    prog = pt.program_metrics(dict(res["ctx"], program_trace=rec))
    assert set(prog) == set(pt.READERS)
    assert 0.0 < prog["slot_occupancy"] <= 100.0
    assert 0.0 <= prog["ret_device_row_share"] <= 100.0
