"""One run of one cell: build, warm up, measure, check, report.

The order is fixed: the stack is built and warmed (``setup_s`` runs from
process start to the window's start); load is offered for ``--seconds``
and each request due in the window is followed until it finishes; the
device's peak memory is read; the program's state is freed; then the
plain references check a sample of what the timed path produced.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from bench import correct, loadgen, manifest, stack as stack_mod, stats, traffic
from bench.spans import Spans


class CompileMeter:
    """Counts programs lowered and compiled, as JAX reports them."""

    def __init__(self):
        import jax.monitoring

        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def require_chips(n: int):
    """The devices of the cell, or exit: a measurement with no TPU is none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {devices[0].platform}; nothing was run")
    if len(devices) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def enable_cache() -> str:
    import jax

    from repro.launch.serve import enable_compile_cache

    path = enable_compile_cache()
    # small programs (one scan shape, one slab update) compile in well under
    # a second; keep them too, or every run compiles them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class ScanRecorder:
    """Records each call of the device scan: when it was made, its shape
    (query groups G, top-k width k), and the slab slots and valid-row
    counts it was given, kept as the device arrays they were (read once
    the window has closed), so a reader can count the rows each group had
    to scan."""

    def __init__(self):
        import repro.kernels.ivf_scan as pkg

        self.pkg = pkg
        self.orig = pkg.ivf_scan
        self.calls: list = []  # (wall time, G, k, slots, valid, (slab shape, QB, item size))
        pkg.ivf_scan = self._scan

    def _scan(self, q, slots, slab, valid, k, *, impl):
        self.calls.append((time.perf_counter(), int(q.shape[0]), int(k), slots, valid,
                           (tuple(slab.shape), int(q.shape[1]), slab.dtype.itemsize)))
        return self.orig(q, slots, slab, valid, k, impl=impl)

    def shapes(self, t0: float) -> dict:
        """(G, k) -> calls made from ``t0`` on."""
        out: dict = {}
        for t, g, k, *_ in self.calls:
            if t >= t0:
                out[(g, k)] = out.get((g, k), 0) + 1
        return out

    def rows(self, t0: float, t1: float) -> list:
        """(G, k, valid rows of the groups' clusters, slab shape, query rows
        a group, slab item size) of each call made in [t0, t1)."""
        return [(g, k, int(np.asarray(valid)[np.asarray(slots)].sum()), *meta)
                for t, g, k, slots, valid, meta in self.calls if t0 <= t < t1]

    def restore(self) -> None:
        self.pkg.ivf_scan = self.orig


def run(root: Path, spec: dict, seed: int, seconds: float, trace_on: bool,
        t_start: float, *, devices=None, fault=None, with_controls=False) -> dict:
    import jax

    config, t = spec["config"], spec["traffic"]
    meter = CompileMeter()
    spans = Spans(annotate=trace_on)
    items = traffic.schedule(t, seed, seconds)
    st = stack_mod.build(config, t, seed, spans, [i for _, _, i in items])
    scans = ScanRecorder()
    uploads: dict = {}  # slots staged -> uploads
    search = st.server.backend.search_charged

    def search_noting_uploads(work, worker_id=0):
        before = st.hybrid.upload_stats["delta_slots"]
        out = search(work, worker_id)
        if st.hybrid.upload_stats["delta_slots"] > before:
            n = st.hybrid.upload_stats["delta_slots"] - before
            uploads[n] = uploads.get(n, 0) + 1
        return out

    st.server.backend.search_charged = search_noting_uploads
    ret_log = correct.RetrievalLog(st.server.sched)
    sizes = st.index.cluster_sizes()
    index_info = (f"index: {int(sizes.sum())} passages in {len(sizes)} lists, rows a list "
                  f"p50 {int(np.median(sizes))} p99 {int(np.percentile(sizes, 99))} "
                  f"max {int(sizes.max())}; {int((sizes > st.hybrid.tile_len).sum())} "
                  f"lists longer than the {st.hybrid.tile_len}-row device tile")
    warm_info = stack_mod.warm(st, config, t, spans)
    if fault is not None:
        fault(st)

    lg = loadgen.OpenLoop(st.server, [(d, w) for d, w, _ in items], seconds, dict(t["limits_s"]),
                          float(t["follow_s"]))
    prof = _Profiler(trace_on, float(t.get("trace_s", seconds)))
    cache0 = {}
    lowered0 = compiled0 = 0

    def at_window_start(now):
        nonlocal lowered0, compiled0
        if not cache0 and now >= lg.t0:
            s = st.hybrid.stats()
            cache0.update(hits=s["hits"], misses=s["misses"])
            lowered0, compiled0 = meter.lowered, meter.compiled
            uploads.clear()

    def poll(now):
        at_window_start(now)
        prof.poll(now, lg.t0)

    lg.on_poll = poll
    lead = float(t.get("lead_s", 0.0))
    lg.run(lead)
    t_window0, t_window1 = lg.t0, lg.t0 + seconds
    prof.finish()
    in_window = {"lowered": meter.lowered - lowered0,
                 "compiled": meter.compiled - compiled0}
    s1 = st.hybrid.stats()
    cache1 = {"hits": s1["hits"], "misses": s1["misses"]}
    devs = devices or jax.devices()[:1]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)

    reqs = lg.window_requests()
    sent = [r["sent"] - r["due"] for r in reqs]
    done_ids = {r.request_id for r in st.server.sched.done}
    ticket_ids = [(tk.status, tk.request_id) for due, _, _, tk in lg.sent
                  if 0 <= due < seconds]
    accounting = sum(1 for status, rid in ticket_ids
                     if (status == "finished") != (rid is not None and rid in done_ids))

    ctx = {
        "seconds": seconds, "window": (t_window0, t_window1), "spans": spans,
        "requests": reqs, "gen_counts": dict(st.gen.counts), "cache0": cache0,
        "cache1": cache1, "trace": prof.reduced,
        "trace_window": prof.window, "model": stack_mod.model_dict(config),
        "scan_rows": scans.rows(*prof.window) if prof.window else [],
        "device_kind": devs[0].device_kind, "config": config,
    }
    unanswered = sum(1 for r in reqs if r["done"] is None)
    sample = correct.take_sample(st, ret_log, seed, config, unanswered)
    scans.restore()
    st.gen.restore()
    # free the program's state before the references run: a process's peak
    # never falls, and the references must not set it
    del st, lg, ret_log
    gc.collect()
    checks = correct.check(sample, config, seed, accounting)
    ctrl = correct.controls(sample, config, seed) if with_controls else None
    return {"ctx": ctx, "checks": checks, "controls": ctrl, "peak": peak,
            "in_window": in_window,
            "warm": warm_info, "scan_shapes": scans.shapes(t_window0),
            "upload_sizes": dict(uploads), "lateness": sent,
            "skew": sample.skew, "ret_stages": sample.n_ret_stages,
            "setup_s": t_window0 - t_start, "index_info": index_info,
            "setup_spans": {n: b - a for n, a, b, _ in spans.rows if n.startswith("setup.")}}


class _Profiler:
    """Traces the first ``trace_s`` seconds of the window, when asked.  A
    timer thread ends the trace, so a long scheduler step cannot stretch
    it; the trace is read once the run is over."""

    def __init__(self, on: bool, trace_s: float):
        self.on, self.trace_s = on, trace_s
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.state = "idle"
        self.window = None
        self.reduced = None
        self.timer = None
        self.lock = threading.Lock()

    def poll(self, now: float, t0: float) -> None:
        import jax

        if not self.on or self.state != "idle" or now < t0:
            return
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation("bench.mark"):
            pass
        self.state, self.t_start = "tracing", now
        self.timer = threading.Timer(self.trace_s, self._end)
        self.timer.start()

    def _end(self) -> None:
        import jax

        with self.lock:
            if self.state != "tracing":
                return
            with jax.profiler.TraceAnnotation("bench.mark"):
                pass
            self.window = (self.t_start, time.perf_counter())
            jax.profiler.stop_trace()
            self.state = "stopped"

    def finish(self) -> None:
        from bench import trace

        if self.timer is not None:
            self.timer.cancel()
            self.timer.join()
        self._end()
        if self.state != "stopped":
            return
        events = trace.extract(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        marks = sorted(e["start_ns"] for e in events if e["name"] == "bench.mark")
        self.reduced = trace.reduce(events, marks[0], marks[-1])
        self.reduced["events"] = events
        self.reduced["marks_ns"] = (marks[0], marks[-1])


def metrics_line(root: Path, spec: dict, res: dict, trace_on: bool) -> dict:
    """The contract's metrics: end-to-end without tracing, per-layer with."""
    ctx = res["ctx"]
    out = {}
    if not trace_on:
        reqs = ctx["requests"]
        lat = [x * 1e3 for x in stats.client_latencies(reqs)]
        values = {
            "goodput_rps": stats.goodput(reqs, ctx["seconds"]),
            "latency_p50_ms": stats.percentile(lat, 50),
            "latency_p90_ms": stats.percentile(lat, 90),
            "output_tokens_per_s": stats.emitted(ctx["spans"], *ctx["window"]) / ctx["seconds"],
            "setup_s": res["setup_s"],
        }
        for m in spec["end_to_end"]:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out
    for m in spec["per_layer"]:
        v = manifest.load_reader(root, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def report(root: Path, spec: dict, res: dict, trace_on: bool, devs) -> dict:
    """Print the earlier lines and the compared numbers; return the result."""
    ctx, g = res["ctx"], res["ctx"]["gen_counts"]
    reqs = ctx["requests"]
    print(res["index_info"])
    print("setup seconds by phase: " + ", ".join(
        f"{k[6:]} {v:.3f}" for k, v in res["setup_spans"].items()) + f"; total {res['setup_s']:.3f}")
    late = res["lateness"]
    if late:
        print(f"load generator lateness p50 {stats.percentile(late, 50) * 1e3:.3f} ms, "
              f"p99 {stats.percentile(late, 99) * 1e3:.3f} ms over {len(late)} sends")
    c0, c1 = ctx["cache0"], ctx["cache1"]
    hits, miss = c1["hits"] - c0.get("hits", 0), c1["misses"] - c0.get("misses", 0)
    print(f"probe skew: {res['skew']:.4f} of the probes of {res['ret_stages']} "
          f"retrieval stages landed on the most probed eighth of the clusters; "
          f"hot-cache hits {hits}, misses {miss} in the window; warm-up {res['warm']}")
    print(f"generation: prefill tokens executed {g['prefill_executed']} of "
          f"{g['prefill_charged']} charged (engine truncated {g['prefill_truncated']}, "
          f"re-prefilled {g['reprefill_tokens']} after {g['evictions']} evictions); "
          f"decode steps executed {g['steps_executed']} of {g['steps_charged']} charged; "
          f"tokens {g['tokens_executed']} of {g['tokens_charged']} charged "
          f"(+{g['tokens_outside_batch']} outside the batch); calls {g['calls']}, "
          f"mismatched {g['mismatched_calls']}")
    by_k: dict = {}
    for (g_, k_), n_ in sorted(res["scan_shapes"].items()):
        by_k.setdefault(k_, []).append(f"{g_}:{n_}")
    print(f"compiles in the window: {res['in_window']['lowered']} programs lowered, "
          f"{res['in_window']['compiled']} compiled")
    print("scan calls in the window by k as G:calls: "
          + "; ".join(f"k={k_} " + " ".join(v) for k_, v in by_k.items()))
    print("slab uploads in the window as slots:count: " + " ".join(
        f"{n_}:{c_}" for n_, c_ in sorted(res["upload_sizes"].items())))
    print(f"peak bytes in use after the window: {res['peak']}")
    finished = sum(1 for r in reqs if r["done"] is not None)
    checks = res["checks"]
    ok = all(c["ok"] for c in checks)
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']}) {c['detail']}",
              file=sys.stderr)
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": res["peak"]}
    line = {"correct": ok, "attempted": len(reqs), "failed": len(reqs) - finished,
            "metrics": metrics_line(root, spec, res, trace_on), "device": dev}
    if trace_on and ctx["trace"] is not None:
        red = ctx["trace"]
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["compared"] = compared
    return line


def main(argv, t_start: float, root: Path) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = manifest.resolve_cell(root, manifest.load_manifest(root), args.workload)
    devs = require_chips(int(spec["cell"]["chips"]))
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache: {enable_cache()}")
    res = run(root, spec, args.seed, args.seconds, bool(args.trace), t_start, devices=devs)
    line = report(root, spec, res, bool(args.trace), devs)
    print(json.dumps(line))
    return 0
