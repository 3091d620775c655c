"""Serving launcher: the JAX generation engine and the hybrid retrieval
engine behind the HedraRAG wavefront scheduler, on one device.

The model is built at its published widths with random weights from
``PRNGKey(0)``; ``--reduced`` swaps in the tiny same-family config for CPU
runs.  Retrieval kernels resolve to Pallas on a TPU and to the jnp oracle
elsewhere.  ``chip_smoke.py`` at the repository root drives ``build_server``
on one TPU chip.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ModelConfig
from repro.core.backends import RealBackend
from repro.models import lm
from repro.retrieval import (
    CorpusConfig,
    HybridRetrievalEngine,
    IVFIndex,
    SyntheticEmbedder,
    make_corpus,
)
from repro.server import Server
from repro.serving.engine import GenerationEngine
from repro import workflows

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    a fixed path, since the path is part of what a later run must find again.
    Call it from an entry point before the first ``jit``, never on import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(ARCH_IDS))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family config (CPU runs) "
                         "instead of the published widths")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--workflow", default="one-shot",
                    choices=list(workflows.WORKFLOWS))
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--ret-workers", type=int, default=1,
                    help="size of the retrieval worker pool")
    ap.add_argument("--dispatch", default="affinity",
                    choices=["affinity", "least_loaded", "round_robin"],
                    help="retrieval sub-stage placement policy")
    ap.add_argument("--index-sharding", action="store_true",
                    help="distributed IVF retrieval: each worker owns a "
                         "contiguous cluster-range shard; sub-stages "
                         "scatter-gather across the pool")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="inject a seeded random FaultPlan (crashes/stalls/"
                         "transients) and serve through the recovery path")
    ap.add_argument("--fault-crash-frac", type=float, default=0.25,
                    help="fraction of the pool crashed by the fault plan")
    ap.add_argument("--fault-transient-prob", type=float, default=0.05,
                    help="per-dispatch transient failure probability")
    ap.add_argument("--wallclock", action="store_true",
                    help="serve through the threaded wall-clock ingress "
                         "(serving/ingress.py) instead of the batch path; "
                         "arrivals are real producer-thread timestamps")
    ap.add_argument("--speedup", type=float, default=1.0,
                    help="wall->virtual clock compression for --wallclock "
                         "(1 wall ms = speedup virtual ms).  The backend "
                         "charges measured time, so 1 keeps heartbeats on "
                         "the same clock as the work they interleave with")
    ap.add_argument("--closed-loop", type=int, default=0, metavar="CLIENTS",
                    help="with --wallclock: closed-loop load generation with "
                         "this many client threads (submit, wait, think, "
                         "repeat) instead of an open-loop stream")
    ap.add_argument("--replay-check", action="store_true",
                    help="with --wallclock: record the measured backend "
                         "charges on a DurationTape alongside the arrival "
                         "trace, replay both on a fresh server stack over "
                         "the pure virtual clock, and assert bit-identical "
                         "per-request event fingerprints (the determinism "
                         "oracle, extended to the measured RealBackend)")
    ap.add_argument("--arrivals-out", metavar="PATH", default=None,
                    help="with --wallclock: write the recorded "
                         "arrival/heartbeat trace JSON here")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="record spans on the virtual and the wall clock "
                         "and write a Chrome trace-event / Perfetto JSON "
                         "timeline here (implies tracing=True)")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="sample the labeled metrics registry and write the "
                         "JSON snapshot (with an embedded Prometheus text "
                         "exposition) here (implies telemetry=True)")
    return ap


def model_config(args) -> ModelConfig:
    cfg = get_config(args.arch)
    return cfg.reduced() if args.reduced else cfg


def init_params(cfg: ModelConfig):
    """Random weights from ``PRNGKey(0)``, made on the device in the
    config's dtype: under ``jit`` each float32 draw fuses into its cast, so
    no float32 copy of a weight is held."""
    return jax.jit(lm.init_params, static_argnums=0)(cfg, jax.random.PRNGKey(0))


def build_server(args, cfg: ModelConfig, params, *, fault_plan=None,
                 cache_update_interval: int = 50) -> Server:
    """The whole serving stack for one pass: seeded synthetic corpus, IVF
    index, hybrid retrieval engine with an 8-cluster device slab, a warmed
    generation engine, the measured ``RealBackend`` and the hedra ``Server``.

    Rebuilt from scratch for each pass (the replay oracle needs a fresh,
    bit-identical stack: engine KV state and the hybrid cache are mutated by
    a run).  The engine's prefill widths and decode step are compiled here,
    so no compilation lands in the timed window.
    """
    docs, _, topics = make_corpus(CorpusConfig(n_docs=8000, dim=48, n_topics=64))
    index = IVFIndex.build(docs, n_clusters=32, iters=4)
    embedder = SyntheticEmbedder(topics)
    hybrid = HybridRetrievalEngine(index, cache_capacity=8,
                                   update_interval=cache_update_interval)
    engine = GenerationEngine(cfg, params, max_batch=8, max_len=160,
                              eos_id=0)
    engine.warmup(args.max_new)
    backend = RealBackend(engine, index, embedder, hybrid=hybrid)
    pending = [f"query {i}" for i in range(args.n_requests)]
    orig = backend.gen_duration

    def gen_duration(n_prefill_tokens, batch, n_steps):
        while engine.can_admit() and pending:
            p = pending.pop(0)
            toks = (np.frombuffer(p.encode(), np.uint8).astype(np.int32)
                    % (cfg.vocab_size - 2)) + 1
            engine.add_sequence(toks, max_new=args.max_new)
        return orig(n_prefill_tokens, batch, n_steps)

    backend.gen_duration = gen_duration
    return Server(index, embedder, mode="hedra", backend=backend,
                  nprobe=8,
                  num_ret_workers=args.ret_workers,
                  dispatch_policy=args.dispatch,
                  index_sharding=args.index_sharding,
                  fault_plan=fault_plan,
                  external_heartbeats=args.wallclock,
                  fault_tolerance=args.wallclock,
                  tracing=args.trace_out is not None,
                  telemetry=args.metrics_out is not None)


def main(argv: Optional[list[str]] = None) -> None:
    args = make_parser().parse_args(argv)
    enable_compile_cache()
    cfg = model_config(args)
    params = init_params(cfg)
    fault_plan = None
    if args.fault_seed is not None:
        from repro.serving.faults import FaultPlan

        horizon = args.n_requests * 20_000.0 + 400_000.0
        fault_plan = FaultPlan.random(
            args.fault_seed, args.ret_workers, horizon,
            crash_frac=args.fault_crash_frac,
            transient_prob=args.fault_transient_prob)
        print(f"fault plan: {fault_plan.describe()}")

    def build() -> Server:
        return build_server(args, cfg, params, fault_plan=fault_plan)

    server = build()
    if args.trace_out:
        server.wall_trace()  # the exported trace carries both clocks
    t0 = time.perf_counter()
    if args.wallclock:
        from repro.serving import ingress
        from repro.serving.workload import ClosedLoopSpec, MixSpec

        tape = None
        if args.replay_check:
            # RealBackend charges *measured* durations (the sanctioned
            # wall-clock boundary in core/backends.py), so the arrival
            # trace alone cannot reproduce its timeline — record the
            # charges too and replay them verbatim into the replica
            tape = ingress.DurationTape()
            ingress.tape_backend(server.backend, tape, mode="record")
        if args.closed_loop > 0:
            spec = ClosedLoopSpec(
                name=args.workflow,
                weights={args.workflow: 1.0},
                num_clients=args.closed_loop,
                requests_per_client=max(
                    1, args.n_requests // args.closed_loop))
            m, trace = server.serve_wallclock(closed_loop=spec,
                                              speedup=args.speedup)
        else:
            mix = MixSpec(args.workflow, weights={args.workflow: 1.0})
            stream = mix.sample(args.n_requests, rate_per_s=50.0)
            m, trace = server.serve_wallclock(stream, speedup=args.speedup)
        print(f"ingress trace: {len(trace.rows)} rows")
        if args.arrivals_out:
            trace.save(args.arrivals_out)
            print(f"arrival trace written to {args.arrivals_out}")
        if args.replay_check:
            replica = build()
            ingress.tape_backend(replica.backend, tape, mode="replay")
            ingress.replay_trace(replica, trace)
            if replica.fingerprints() != server.fingerprints():
                raise SystemExit("replay-check FAILED: virtual-clock replay "
                                 "diverged from the wall-clock run")
            print(f"replay-check ok: virtual-clock replay is bit-identical "
                  f"({len(tape.rows)} taped backend charges, "
                  f"{tape.remaining()} unconsumed)")
    else:
        for i in range(args.n_requests):
            server.add_request(f"query {i}", workflows.build(args.workflow),
                               arrival_us=i * 20_000.0)
        m = server.run()
    print(f"served {m.finished} requests in {time.perf_counter()-t0:.2f}s wall")
    for k, v in m.summary().items():
        print(f"  {k:24s} {v}")
    if args.trace_out:
        server.export_trace(args.trace_out)
        print(f"trace written to {args.trace_out} "
              "(open in https://ui.perfetto.dev or chrome://tracing)")
    if args.metrics_out:
        server.metrics_snapshot(args.metrics_out)
        print(f"metrics snapshot written to {args.metrics_out}")


if __name__ == "__main__":
    main()
