"""Bring-up smoke of the served path on a TPU.

    python chip_smoke.py              # one chip: qwen3-1.7b + hybrid retrieval
    python chip_smoke.py --four-chip  # four chips: sharded IVF search only

The one-chip phase builds the serving stack of ``repro.launch.serve`` at the
published widths of qwen3-1.7b (random bf16 weights from ``PRNGKey(0)``),
with a device hot slab of IVF clusters scanned by the Pallas kernel, and
serves one-shot and HyDE requests through ``Server.serve_wallclock`` with
the measured ``RealBackend``.  It then checks what came out: every request
finished, the device scans ran the Pallas kernel and agree with a float64
NumPy scan of the same slab rows, generated tokens are in the vocabulary and
prefill logits are finite.

The four-chip phase runs ``make_sharded_search`` over a ``data`` mesh of
four chips with a 1M x 768 float32 slab sharded across them, and compares it
with ``reference_search`` on one chip.

There is no CPU fallback: without a TPU the script exits non-zero before it
builds anything.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# The kernel computes squared L2 in float32 with HIGHEST matmul precision.
# Corpus vectors and queries are unit-norm, so distances lie in [0, 4] and
# float32 rounding stays near 1e-6; 1e-4 leaves two orders of headroom.
TOPK_TOL = 1e-4
# The sharded search and its one-chip reference use the same default matmul
# precision, so they differ only in accumulation order (~1e-6 for unit-norm
# rows); a real fault (a lost shard, a wrong row offset) moves a distance by
# far more than 1e-3.
SHARDED_TOL = 1e-3
N_REQUESTS = 8


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                           f"{devices[0].platform}); nothing was run")
    check(len(devices) >= n_chips,
          f"need {n_chips} TPU chips, JAX sees {len(devices)}")
    return devices


class CompileMeter:
    """Sums JAX's compile-phase durations (tracing, lowering, backend
    compile) as they are reported, and counts backend compiles."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.compiles += name.endswith("backend_compile_duration")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# One chip: the served path
# ---------------------------------------------------------------------------


def check_topk(call) -> int:
    """One recorded device scan against a float64 NumPy scan of the same
    slab rows.  Distances agree to TOPK_TOL at every rank; the id sets agree
    exactly wherever the k-th and (k+1)-th reference distances are more than
    TOPK_TOL apart.  Returns the number of query rows checked."""
    q, slots, slab, valid, k, dists, idx = call
    for g, slot in enumerate(slots):
        n = int(valid[slot])
        rows = slab[slot, :n].astype(np.float64)
        d2 = ((q[g].astype(np.float64)[:, None, :] - rows[None]) ** 2).sum(-1)
        order = np.argsort(d2, axis=1, kind="stable")
        ref = np.take_along_axis(d2, order, axis=1)
        kk = min(k, n)
        check(np.allclose(dists[g, :, :kk], ref[:, :kk], rtol=0, atol=TOPK_TOL),
              f"scan distances differ from float64 beyond {TOPK_TOL} "
              f"(slot {slot}, max err "
              f"{np.abs(dists[g, :, :kk] - ref[:, :kk]).max():.3g})")
        check(bool(np.all(idx[g, :, kk:] == -1)), "padded ranks carry ids")
        for r in range(q.shape[1]):
            if kk < n and ref[r, kk] - ref[r, kk - 1] <= TOPK_TOL:
                continue  # near tie at the cut: either neighbour is right
            check(set(idx[g, r, :kk].tolist()) == set(order[r, :kk].tolist()),
                  f"scan top-{kk} ids differ from float64 (slot {slot}, "
                  f"row {r})")
    return len(slots) * q.shape[1]


def check_kernel(hybrid, calls) -> str:
    """The device scan of the served path is the Pallas kernel."""
    from repro.kernels.ivf_scan import ops as ivf_ops

    impl = ivf_ops.resolve_impl(hybrid.kernel_impl)
    check(impl == "pallas", f"device scan resolved to {impl!r}, not pallas")
    q, slots, slab, valid, k = calls[0][:5]
    hlo = ivf_ops.ivf_scan.lower(q, slots, slab, valid, k,
                                 impl=hybrid.kernel_impl).as_text()
    check("tpu_custom_call" in hlo, "no Pallas kernel in the scan program")
    return impl


def serve_phase(serve_argv: list[str]) -> None:
    import jax

    import repro.kernels.ivf_scan as ivf_pkg
    from repro.launch import serve
    from repro.serving.engine import jit_prefill

    meter = CompileMeter()
    args = serve.make_parser().parse_args(
        ["--wallclock", "--n-requests", str(N_REQUESTS), *serve_argv])
    cfg = serve.model_config(args)

    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg))
    leaves = jax.tree.leaves(params)
    n_params = sum(int(x.size) for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_head={cfg.d_head} "
          f"vocab={cfg.vocab_size}")
    print(f"params: {n_params} ({n_params / 1e9:.3f}e9) dtypes={dtypes}")
    # refresh the 8-slot hot slab every 2 sub-stages so a short run reaches
    # the device path (the launcher's 50 is the paper's steady-state period)
    server = serve.build_server(args, cfg, params, cache_update_interval=2)
    setup_s = time.perf_counter() - t0
    compile_s, n_compiles = meter.seconds, meter.compiles
    print(f"setup seconds: {setup_s:.3f} (compile seconds: {compile_s:.3f} "
          f"over {n_compiles} programs)")

    engine = server.backend.gen_engine
    hybrid = server.backend.hybrid
    tokens: list[int] = []
    step = engine.step

    def recording_step():
        out = step()
        tokens.extend(out.values())
        return out

    engine.step = recording_step
    calls = []
    scan = ivf_pkg.ivf_scan

    def recording_scan(q, slots, slab, valid, k, *, impl):
        # copy now: the hybrid engine reuses its host buffers, and a CPU
        # array may alias them
        snap = [np.array(x, copy=True) for x in (q, slots, slab, valid)]
        dists, idx = scan(q, slots, slab, valid, k, impl=impl)
        calls.append((*snap, k, np.asarray(dists), np.asarray(idx)))
        return dists, idx

    ivf_pkg.ivf_scan = recording_scan
    stream = [(i * 20_000.0, f"what is retrieval augmented generation {i}?",
               "one-shot" if i % 2 else "hyde") for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    try:
        m, trace = server.serve_wallclock(stream, speedup=args.speedup)
    finally:
        ivf_pkg.ivf_scan = scan
        engine.step = step
    serve_s = time.perf_counter() - t0
    summary = m.summary()
    stats = hybrid.stats()
    print(f"serve wall seconds: {serve_s:.3f} (compiles in window: "
          f"{meter.compiles - n_compiles}, "
          f"{meter.seconds - compile_s:.3f} s)")
    print(f"requests: submitted={summary['submitted']} "
          f"finished={summary['finished']} shed={summary['shed']} "
          f"degraded={summary['degraded_completions']} "
          f"gen_tokens={summary['gen_tokens']}")
    print(f"hot cache: hits={stats['hits']} misses={stats['misses']} "
          f"swaps={stats['swaps']} device scans={len(calls)}")

    check(summary["submitted"] == N_REQUESTS,
          f"submitted {summary['submitted']} of {N_REQUESTS}")
    check(summary["finished"] == summary["submitted"],
          f"finished {summary['finished']} of {summary['submitted']}")
    check(summary["shed"] == 0 and summary["shed_final"] == 0, "requests shed")
    check(stats["hits"] > 0 and calls, "no device scan ran")
    impl = check_kernel(hybrid, calls)
    n_rows = sum(check_topk(c) for c in calls)
    print(f"device scan: impl={impl}, top-k of {n_rows} query rows in "
          f"{len(calls)} scans match float64 (tol {TOPK_TOL})")

    check(bool(tokens), "no token was generated")
    toks = np.asarray(tokens)
    check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
          "generated token out of [0, vocab)")
    width = engine.prefill_widths(args.max_new)[0]
    prompt = (np.arange(width, dtype=np.int32) % (cfg.vocab_size - 2) + 1)[None]
    logits, _ = jit_prefill(params, cfg, prompt, max_len=engine.max_len)
    logits = np.asarray(logits)
    check(logits.shape == (1, cfg.vocab_size), f"logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), "prefill logits are not finite")
    print(f"generation: {toks.size} decoded tokens in [0, {cfg.vocab_size}), "
          f"prefill logits finite")
    print(f"peak bytes in use: {peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# Four chips: the sharded IVF search
# ---------------------------------------------------------------------------


def four_chip_phase(n_clusters: int = 2048, tile_len: int = 512,
                    dim: int = 768, n_queries: int = 64, k: int = 10) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.retrieval.distributed import make_sharded_search, reference_search

    meter = CompileMeter()
    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices), ("data",))

    def make_data(key):
        ks, kv, kq = jax.random.split(key, 3)
        slab = jax.random.normal(ks, (n_clusters, tile_len, dim), jnp.float32)
        slab = slab / jnp.linalg.norm(slab, axis=-1, keepdims=True)
        valid = jax.random.randint(kv, (n_clusters,), 1, tile_len + 1)
        q = jax.random.normal(kq, (n_queries, dim), jnp.float32)
        return slab, valid.astype(jnp.int32), q / jnp.linalg.norm(
            q, axis=-1, keepdims=True)

    shardings = (NamedSharding(mesh, P("data", None, None)),
                 NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
    slab, valid, q = jax.block_until_ready(
        jax.jit(make_data, out_shardings=shardings)(jax.random.PRNGKey(0)))
    per_chip = slab.nbytes // len(devices)
    print(f"slab: {n_clusters}x{tile_len}x{dim} f32 = {slab.nbytes} bytes, "
          f"{per_chip} per chip over {len(devices)} chips")

    search = make_sharded_search(mesh, k)
    t0 = time.perf_counter()
    jax.block_until_ready(search(q, slab, valid))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dists, rows = jax.block_until_ready(search(q, slab, valid))
    search_s = time.perf_counter() - t0
    print(f"sharded search: first call {first_s:.3f} s (compile seconds: "
          f"{meter.seconds:.3f}), steady call {search_s:.6f} s for "
          f"{n_queries} queries")
    peaks = [peak_bytes(d) for d in devices]

    one = devices[0]
    ref = jax.jit(reference_search, static_argnums=3)
    ref_d, ref_r = ref(jax.device_put(q, one), jax.device_put(slab, one),
                       jax.device_put(valid, one), k + 1)
    dists, rows, ref_d, ref_r = map(np.asarray, (dists, rows, ref_d, ref_r))
    check(np.allclose(dists, ref_d[:, :k], rtol=0, atol=SHARDED_TOL),
          f"sharded distances differ from reference_search (max err "
          f"{np.abs(dists - ref_d[:, :k]).max():.3g})")
    clear = ref_d[:, k] - ref_d[:, k - 1] > SHARDED_TOL
    for r in np.flatnonzero(clear):
        check(set(rows[r].tolist()) == set(ref_r[r, :k].tolist()),
              f"sharded top-{k} rows differ from reference_search (query {r})")
    print(f"sharded top-{k} matches reference_search on one chip: distances "
          f"to {SHARDED_TOL}, row sets on {int(clear.sum())}/{n_queries} "
          f"queries without a near tie at rank {k}")
    print(f"peak bytes in use per chip (before the one-chip reference): {peaks}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded IVF search over four chips "
                         "and its one-chip reference")
    args = ap.parse_args()
    n_chips = 4 if args.four_chip else 1
    devices = require_tpu(n_chips)

    from repro.launch.serve import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache: "
          f"{enable_compile_cache()}")
    if args.four_chip:
        four_chip_phase()
    else:
        serve_phase([])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
