"""The recorder's wall-clock channel: off it reads no clock, on it changes
no scheduling decision, its spans nest and land in the profiler's trace,
and ``RealBackend``'s measured charges are the durations of its spans."""
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.backends import RealBackend, SimBackend
from repro.models import lm
from repro.obs.trace import NOSPAN, TraceRecorder, validate_trace
from repro.retrieval import HybridRetrievalEngine
from repro.retrieval.ivf import TopK
from repro.server import Server
from repro.serving import ingress
from repro.serving.engine import GenerationEngine
from repro.serving.workload import MIXES


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen3-1.7b").reduced()
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _sim_server(index, emb):
    hybrid = HybridRetrievalEngine(index, cache_capacity=8, update_interval=2,
                                   transit_substages=0, kernel_impl="ref")
    backend = SimBackend(index, emb, hybrid=hybrid)
    return Server(index, emb, mode="hedra", nprobe=8, backend=backend,
                  workload=MIXES["retrieval-heavy"].profile())


def _stream():
    return MIXES["retrieval-heavy"].sample(10, rate_per_s=200.0, seed=5)


def _serve_engine(cfg, params) -> GenerationEngine:
    eng = GenerationEngine(cfg, params, max_batch=2, max_len=96, eos_id=-1)
    eng.add_sequence(np.arange(6) % 200 + 1, max_new=4)
    eng.add_sequence(np.arange(9) % 200 + 1, max_new=4)
    for _ in range(3):
        eng.step()
    return eng


def _parents_enclose(spans) -> bool:
    by_sid = {s.sid: s for s in spans}
    return all(by_sid[s.parent].t0 <= s.t0 and s.t1 <= by_sid[s.parent].t1
               for s in spans if s.parent >= 0)


def test_channel_off_reads_no_clock(small_index, embedder, tiny_model,
                                    monkeypatch):
    def no_clock():
        raise AssertionError("a span site read the clock with tracing off")

    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    s = _sim_server(small_index, embedder)
    m, _ = s.serve_wallclock(_stream(), speedup=1000.0, max_wall_s=60.0)
    assert m.finished == 10
    assert s.sched.trace.wall_spans == [] and s.sched.trace.counters == []
    eng = _serve_engine(*tiny_model)
    assert eng.trace.wall_spans == []


def test_wall_tracing_keeps_fingerprints(small_index, embedder):
    """A wall run recorded with the channel on replays to the same per-request
    fingerprints on a fresh server with the channel off and with it on."""
    s1 = _sim_server(small_index, embedder)
    rec = s1.wall_trace()
    m1, arrivals = s1.serve_wallclock(_stream(), speedup=1000.0,
                                      max_wall_s=60.0)
    assert m1.finished == 10 and rec.wall_spans
    replays = []
    for on in (False, True):
        s = _sim_server(small_index, embedder)
        s.wall_trace(on)
        ingress.replay_trace(s, arrivals)
        replays.append(s.fingerprints())
    assert replays[0] == replays[1] == s1.fingerprints()


def test_wall_spans_nest_and_export(small_index, embedder):
    s = _sim_server(small_index, embedder)
    rec = s.wall_trace()
    s.serve_wallclock(_stream(), speedup=1000.0, max_wall_s=60.0)
    names = {sp.name for sp in rec.wall_spans}
    assert {"serve.queue", "sched.admit", "serve.done", "sched.cycle",
            "sched.gen_substage", "ret.partition"} <= names
    assert _parents_enclose(rec.wall_spans)
    assert any(c[1] == "ret.scanned" for c in rec.counters)
    trace = s.export_trace()
    assert validate_trace(trace) == []
    wall = [e for e in trace["traceEvents"] if e["pid"] == 2 and e["ph"] == "X"]
    assert len(wall) == len(rec.wall_spans)


def test_measured_charge_is_the_span_duration(small_index, embedder):
    backend = RealBackend(None, small_index, embedder,
                          hybrid=HybridRetrievalEngine(small_index,
                                                       kernel_impl="ref"))
    backend.trace.set_wall(True)

    class Task:
        fanout = 1
        cost_us = 0.0

        def execute(self):
            time.sleep(0.002)
            return 7

    charge, fn = backend.stage_charged(Task(), worker_id=1)
    assert fn() == 7
    q = embedder.embed_query(0, 0)
    work = [(q, int(c), TopK.empty(5))
            for c in small_index.probe_order(q[None], 4)[0]]
    charge2, _ = backend.search_charged(work, worker_id=0)
    spans = backend.trace.wall_spans
    by_name = {sp.name: sp for sp in spans if sp.parent < 0}
    assert by_name["stage.run"].dur_us == charge >= 2000.0
    assert by_name["ret.substage"].dur_us == charge2 > 0.0
    # off, the charge is still measured, and nothing is recorded
    backend.trace.set_wall(False)
    charge3, _ = backend.stage_charged(Task(), worker_id=1)
    assert charge3 >= 2000.0 and len(backend.trace.wall_spans) == len(spans)


def test_spans_match_profiler_annotations(tiny_model, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = tiny_model
    _serve_engine(cfg, params)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng = GenerationEngine(cfg, params, max_batch=2, max_len=96, eos_id=-1)
        eng.trace.set_wall(True)
        eng.add_sequence(np.arange(6) % 200 + 1, max_new=4)
        for _ in range(2):
            eng.step()
        with (eng.trace.span("outer", rids=[1]) if eng.trace.wall else NOSPAN):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    spans = eng.trace.wall_spans
    assert _parents_enclose(spans)
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    ann: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    ann.setdefault(ev.name[6:], []).append(ev.duration_ns)
    mine: dict = {}
    for sp in sorted(spans, key=lambda s: s.t0):
        mine.setdefault(sp.name, []).append(sp.t1 - sp.t0)
    assert sorted(mine) == sorted(ann)
    assert {"engine.prefill", "engine.insert", "engine.decode",
            "engine.decode.pull", "outer"} <= set(mine)
    for name, durs in mine.items():
        assert len(durs) == len(ann[name])
        for a, b in zip(sorted(durs), sorted(ann[name])):
            assert abs(a - b) < 5e5, name


def test_recorder_off_by_default_and_one_switch():
    rec = TraceRecorder()
    assert rec.wall is False
    with rec.span("m") as sp:
        pass
    assert sp.dur_us >= 0.0 and rec.wall_spans == []
    rec.set_wall(True)
    with rec.span("m", rids=[3]):
        with rec.span("child"):
            pass
    outer = [s for s in rec.wall_spans if s.name == "m"][0]
    child = [s for s in rec.wall_spans if s.name == "child"][0]
    assert child.parent == outer.sid and outer.parent == -1
    assert outer.args == {"rids": [3]}
