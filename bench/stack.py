"""Build one cell's serving stack from its configuration and traffic files.

Weights, corpus and index come from the seed, the model's configuration and
weights through the configuration's family (``bench/families``); the stack
is the program's own: ``GenerationEngine``, ``HybridRetrievalEngine``,
``RealBackend`` and a ``Server`` in ``hedra`` mode, with the generation load
adapter in place of the backend's generation entry.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import corpus, traffic
from bench.adapters.gen_load import GenLoad


def model_dict(config: dict) -> dict:
    """The published widths with the architecture facts the source implies."""
    return {**config["model"], **config["architecture"]}


def model_config(config: dict):
    """The program's ``ModelConfig`` at the file's widths: a dense GQA +
    SwiGLU stack (the dense family's ``program_config``)."""
    from repro.configs import get_config
    from repro.configs.base import Segment

    m = model_dict(config)
    base = get_config(config["program_arch"])
    return dataclasses.replace(
        base, n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_head=m["head_dim"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"],
        segments=(Segment(mixer="attn", ffn="swiglu",
                          repeat=m["num_hidden_layers"]),),
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        qk_norm=bool(m["qk_norm"]), tie_embeddings=bool(m["tie_word_embeddings"]),
        dtype=m["torch_dtype"],
        kv_cache_dtype=config["engine"].get("kv_cache_dtype", "bf16"))


@dataclasses.dataclass
class Stack:
    server: object
    engine: object
    hybrid: object
    index: object
    embedder: object
    gen: GenLoad
    cfg: object


def build(config: dict, t: dict, seed: int, spans, identities: list, family) -> Stack:
    from repro.core.backends import RealBackend
    from repro.retrieval import HybridRetrievalEngine
    from repro.server import Server
    from repro.serving.engine import GenerationEngine

    r, e = config["retrieval"], config["engine"]
    with spans.span("setup.corpus"):
        index, topics = corpus.build_index(r, seed)
    cfg = family.program_config(config)
    with spans.span("setup.weights"):
        params = family.program_params(model_dict(config), seed)
    hybrid = HybridRetrievalEngine(index, cache_capacity=int(r["hot_clusters"]),
                                   tile_len=int(r["tile_len"]),
                                   update_interval=int(r["update_interval"]))
    engine = GenerationEngine(cfg, params, max_batch=int(e["slots"]),
                              max_len=int(e["max_len"]), eos_id=-1)
    embedder = corpus.make_embedder(r, topics, seed)
    backend = RealBackend(engine, index, embedder, hybrid=hybrid)
    server = Server(index, embedder, mode="hedra", backend=backend,
                    workload=traffic.profile(t, identities), nprobe=int(r["ivf_nprobe"]),
                    max_gen_batch=int(e["max_gen_batch"]))
    gen = GenLoad(server.sched, engine, server.workload, spans,
                  vocab=cfg.vocab_size, seed=seed,
                  max_new=int(t["profile"]["max_gen_tokens"]) + 1)
    gen.install(backend)
    spans.wrap(backend, "gen_duration", "gen")
    spans.wrap(backend, "search_charged", "search")
    spans.wrap(backend, "stage_charged", "stage")
    spans.wrap(server, "step", "sched_step")
    return Stack(server, engine, hybrid, index, embedder, gen, cfg)


def scan_widths(t: dict, tile_len: int) -> list:
    """Every top-k width ``k`` the device scan can be called with under
    this mix: a sub-stage's width is the largest of its queries' (their
    workflows' retrieval ``topk``, or the scheduler's speculative width),
    cut to the tile."""
    from repro import workflows
    from repro.core.ragraph import RetrievalNode
    from repro.core.wavefront import SPEC_RET_K

    ks = {SPEC_RET_K}
    for name in t["workflows"]:
        ks |= {n.topk for n in workflows.build(name).nodes.values()
               if isinstance(n, RetrievalNode)}
    return sorted({min(int(k), tile_len) for k in ks})


def warm(stack: Stack, config: dict, t: dict, spans) -> dict:
    """Compile every shape the cell's traffic uses and fill the hot cache.

    The engine compiles its prefill widths and decode step.  The device
    scan compiles once per query-group count ``G`` and top-k width ``k``:
    every ``k`` the mix's workflows can ask for, and ``G`` up to the mix
    file's ``scan_g_max``.  The hot slab's delta upload compiles a dozen
    small programs per number of slots staged, up to the slab's size; the
    counts up to the mix file's ``upload_slots_max`` are warmed (all 128
    took 991 s to compile on a v5e, most of a first run's allowance; see
    PERF.md).  The hot cache is filled by
    sub-stages of queries drawn like the traffic's (request ids outside the
    run's), so the window starts with the slab holding the popular
    clusters, as a serving deployment would.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.ivf_scan import ivf_scan
    from repro.retrieval.hybrid import QB
    from repro.retrieval.ivf import TopK

    r, w = config["retrieval"], t["warm"]
    cap, tile, dim = int(r["hot_clusters"]), int(r["tile_len"]), int(r["dim"])
    with spans.span("setup.engine"):
        stack.engine.warmup(int(t["profile"]["max_gen_tokens"]) + 1)
    slab = jnp.zeros((cap, tile, dim), jnp.float32)
    valid = jnp.zeros((cap,), jnp.int32)
    with spans.span("setup.scan"):
        # inputs made on the host, as the engine makes them: no program
        # per shape besides the scan's own
        for k in scan_widths(t, tile):
            for g in range(1, int(w["scan_g_max"]) + 1):
                out = ivf_scan(jnp.asarray(np.zeros((g, QB, dim), np.float32)),
                               jnp.asarray(np.zeros((g,), np.int32)), slab, valid, k,
                               impl=stack.hybrid.kernel_impl)
        jax.block_until_ready(out)
    with spans.span("setup.upload"):
        # the engine stages slots with numpy int64 indices and values made
        # on the host; device-made zeros of the same shape compile the same
        # update program without moving a gigabyte per size
        for n in range(1, min(cap, int(w["upload_slots_max"])) + 1):
            slots = np.arange(n, dtype=np.int64)
            jax.block_until_ready(slab.at[slots].set(
                jnp.zeros((n, tile, dim), jnp.float32)))
            jax.block_until_ready(valid.at[slots].set(
                jnp.asarray(np.zeros((n,), np.int32))))
    del slab, valid
    with spans.span("setup.cache"):
        nprobe = int(r["ivf_nprobe"])
        for i in range(int(w["cache_substages"])):
            q = stack.embedder.embed_query((1 << 40) + i, 0)
            probes = stack.index.probe_order(q[None], nprobe)[0]
            stack.hybrid.search_substage(
                [(q, int(c), TopK.empty(5)) for c in probes])
    st = stack.hybrid.stats()
    return {"cache_hits": st["hits"], "cache_misses": st["misses"],
            "cache_swaps": st["swaps"]}
