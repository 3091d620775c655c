"""SoA retrieval-plan executor: reference equivalence, scoreboard merges,
oversized-cluster residency refusal, snapshot consistency, delta uploads."""
import numpy as np
import pytest

from repro.retrieval import (
    HybridRetrievalEngine,
    IVFIndex,
    TopK,
)
from repro.retrieval.plan import (
    BatchTopK,
    PlanBuilder,
    plan_from_work,
    plan_search,
)


# ------------------------------------------------------------ plan executor


def test_plan_search_matches_reference(small_index):
    rng = np.random.default_rng(0)
    q = rng.standard_normal((12, small_index.dim)).astype(np.float32)
    for nprobe, k in [(1, 1), (6, 5), (16, 10), (48, 3), (8, 20)]:
        D, I = small_index.search(q, nprobe, k)
        D2, I2 = plan_search(small_index, q, nprobe, k)
        np.testing.assert_array_equal(I2, I)
        np.testing.assert_allclose(D2, D, rtol=1e-4, atol=1e-4)


def test_plan_matches_legacy_work_list(small_index):
    rng = np.random.default_rng(1)
    q = rng.standard_normal((6, small_index.dim)).astype(np.float32)
    probes = small_index.probe_order(q, 4)
    work = [(q[i], int(probes[i, j]), TopK.empty(5))
            for i in range(6) for j in range(3)]
    ref = small_index.search_cluster_batch(
        [(a, b, TopK(c.k, c.dists.copy(), c.ids.copy())) for a, b, c in work])
    plan = plan_from_work(work)
    res = plan.finalize(small_index.search_plan(plan))
    for g, r in enumerate(ref):
        tk = res.group_topk(g, r.k)
        np.testing.assert_array_equal(tk.ids, r.ids)
        np.testing.assert_allclose(tk.dists, r.dists, rtol=1e-4, atol=1e-5)


def test_batch_topk_merge_rows_matches_scalar_merge():
    rng = np.random.default_rng(2)
    n, k = 6, 5
    batch = BatchTopK.empty(n, k)
    scalars = [TopK.empty(k) for _ in range(n)]
    for _ in range(4):
        cand_d = rng.random((n, 7)).astype(np.float32)
        cand_i = rng.choice(100_000, (n, 7), replace=False).astype(np.int64)
        batch.merge_rows(np.arange(n), cand_d, cand_i)
        scalars = [tk.merge(cand_d[i], cand_i[i])
                   for i, tk in enumerate(scalars)]
    for i, tk in enumerate(scalars):
        np.testing.assert_array_equal(batch.ids[i], tk.ids)
        np.testing.assert_allclose(batch.dists[i], tk.dists, rtol=1e-6)


def test_finalize_streaks_match_sequential_merge(small_index):
    """Vectorized per-cluster streaks == the scalar merge/compare chain."""
    rng = np.random.default_rng(3)
    q = rng.standard_normal((4, small_index.dim)).astype(np.float32)
    probes = small_index.probe_order(q, 6)
    b = PlanBuilder()
    for i in range(4):
        b.add(q[i], probes[i], k=5, no_improve=1)
    plan = b.build()
    res = plan.finalize(small_index.search_plan(plan))
    for g in range(4):
        tk = TopK.empty(5)
        last_kth, noimp = np.inf, 1
        for cid in probes[g]:
            d, ids = small_index.search_cluster(q[g: g + 1], int(cid))
            tk = tk.merge(d[0], ids[0])
            if tk.kth < last_kth - 1e-12:
                noimp, last_kth = 0, tk.kth
            else:
                noimp += 1
        np.testing.assert_array_equal(res.group_topk(g, 5).ids, tk.ids)
        assert int(res.no_improve[g]) == noimp
        assert float(res.last_kth[g]) == pytest.approx(last_kth)


def test_finalize_paths_agree(small_index, monkeypatch):
    """The dense streak-tensor path and the sequential fallback (used for
    large coarse-mode groups) must produce identical results."""
    import repro.retrieval.plan as plan_mod

    rng = np.random.default_rng(4)
    q = rng.standard_normal((5, small_index.dim)).astype(np.float32)
    probes = small_index.probe_order(q, 7)
    b = PlanBuilder()
    for i in range(5):
        b.add(q[i], probes[i], k=4, last_kth=50.0, no_improve=2)
    plan = b.build()
    results = small_index.search_plan(plan)
    dense = plan.finalize(results)
    monkeypatch.setattr(plan_mod, "_STREAK_TENSOR_MAX", 1)
    seq = plan.finalize(results)
    np.testing.assert_array_equal(dense.ids, seq.ids)
    np.testing.assert_array_equal(dense.dists, seq.dists)
    np.testing.assert_array_equal(dense.no_improve, seq.no_improve)
    np.testing.assert_allclose(dense.last_kth, seq.last_kth)


# -------------------------------------------------- hybrid engine regressions


def _manual_index(sizes, dim=16, seed=7):
    """Hand-built IVFIndex with exact cluster sizes (kmeans would rebalance)."""
    rng = np.random.default_rng(seed)
    n = int(sum(sizes))
    flat = rng.standard_normal((n, dim)).astype(np.float32)
    offsets = np.zeros(len(sizes) + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    cent = np.stack([flat[offsets[i]: offsets[i + 1]].mean(0)
                     for i in range(len(sizes))]).astype(np.float32)
    radii = np.array([
        np.linalg.norm(flat[offsets[i]: offsets[i + 1]] - cent[i], axis=1).max()
        for i in range(len(sizes))], np.float32)
    return IVFIndex(
        centroids=cent, flat=flat,
        flat_norms=(flat**2).sum(-1).astype(np.float32),
        ids=np.arange(n, dtype=np.int64), offsets=offsets, radii=radii)


def _oversized_setup():
    """Index with one giant cluster (> tile_len=128) plus small ones."""
    index = _manual_index([400, 60, 60, 60, 60])
    assert int(index.cluster_sizes().max()) > 128
    return index


def test_oversized_cluster_refused_and_paths_agree():
    """A cluster larger than tile_len must stay on the host path (no silent
    truncation) and host/device results must agree."""
    index = _oversized_setup()
    eng = HybridRetrievalEngine(index, cache_capacity=8, tile_len=128,
                                update_interval=1, transit_substages=0,
                                kernel_impl="ref")
    big_cid = int(np.argmax(index.cluster_sizes()))
    rng = np.random.default_rng(8)
    q = rng.standard_normal((6, index.dim)).astype(np.float32) * 0.05
    # hammer the big cluster so the cache wants it resident
    for _ in range(6):
        work = [(q[i], big_cid, TopK.empty(5)) for i in range(6)]
        res = eng.search_substage(work)
    assert eng.cache.stats.oversized_rejects > 0
    assert not eng.cache.is_resident(big_cid)
    # results equal the host reference (would differ if truncated to 128)
    work = [(q[i], big_cid, TopK.empty(5)) for i in range(6)]
    res = eng.search_substage(work)
    ref = index.search_cluster_batch(
        [(q[i], big_cid, TopK.empty(5)) for i in range(6)])
    for r, rr in zip(res, ref):
        np.testing.assert_array_equal(r.ids, rr.ids)
        np.testing.assert_allclose(r.dists, rr.dists, rtol=1e-4, atol=1e-5)


def test_small_clusters_still_cached_next_to_oversized():
    index = _oversized_setup()
    eng = HybridRetrievalEngine(index, cache_capacity=8, tile_len=128,
                                update_interval=1, transit_substages=0,
                                kernel_impl="ref")
    small_cid = 1  # 60 rows < tile_len
    rng = np.random.default_rng(9)
    q = rng.standard_normal((4, index.dim)).astype(np.float32)
    for _ in range(4):
        eng.search_substage([(q[i], small_cid, TopK.empty(3))
                             for i in range(4)])
    assert eng.cache.is_resident(small_cid)


def test_oversized_cluster_rejected_once_and_slot_backfilled():
    """A refused cluster must not pin a slot across refreshes: it is
    rejected at most once and the slot goes to the next-hottest cluster."""
    index = _oversized_setup()  # cluster 0 oversized, 1-4 small
    eng = HybridRetrievalEngine(index, cache_capacity=2, tile_len=128,
                                update_interval=1, transit_substages=0,
                                kernel_impl="ref")
    rng = np.random.default_rng(12)
    q = rng.standard_normal((4, index.dim)).astype(np.float32)
    # cluster 0 hottest, clusters 1 and 2 steadily warm — capacity 2
    for _ in range(6):
        work = [(q[i], 0, TopK.empty(3)) for i in range(4)]
        work += [(q[0], 1, TopK.empty(3)), (q[1], 2, TopK.empty(3))]
        eng.search_substage(work)
    assert eng.cache.stats.oversized_rejects == 1  # refused once, not per refresh
    assert not eng.cache.is_resident(0)
    # both slots hold loadable clusters despite 0 being the hottest
    assert len(eng.cache.resident_ids) == 2


def test_snapshot_partition_survives_swap(small_index):
    """Charge computed from a dispatch-time snapshot must execute correctly
    (host fallback, exact results) even if the cluster is swapped out before
    results_fn runs — the assembly/completion race from the issue."""
    from repro.core.backends import SimBackend
    from repro.retrieval.synthetic import SyntheticEmbedder

    eng = HybridRetrievalEngine(small_index, cache_capacity=2,
                                update_interval=1, transit_substages=0,
                                kernel_impl="ref")
    rng = np.random.default_rng(10)
    q = rng.standard_normal(small_index.dim).astype(np.float32)
    cid = int(small_index.probe_order(q[None], 1)[0][0])
    other = (cid + 1) % small_index.n_clusters
    # make cid resident
    for _ in range(3):
        eng.search_substage([(q, cid, TopK.empty(4))])
    assert eng.cache.is_resident(cid)
    be = SimBackend(small_index, SyntheticEmbedder(np.eye(small_index.dim,
                                                          dtype=np.float32)),
                    hybrid=eng)
    b = PlanBuilder()
    b.add(q, [cid], k=4)
    charge, results_fn = be.search_charged(b.build(), worker_id=0)
    assert charge > 0
    # swap the snapshot's cluster out before completion
    for _ in range(6):
        eng.search_substage([(q, other, TopK.empty(4))] * 3)
    if eng.cache.is_resident(cid):  # force the race deterministically
        slot = eng.cache.slot_of(cid)
        eng._slot_cid[slot] = -2
    batch = results_fn()
    d, ids = small_index.search_cluster(q[None], cid)
    order = np.argsort(d[0], kind="stable")[:4]
    np.testing.assert_array_equal(batch.ids[0][: len(order)], ids[0][order])
    assert eng.cache.stats.stale_fallbacks > 0


def test_empty_substage_still_ticks_cache_clock():
    """search_substage([]) must advance transits/refresh like any sub-stage."""
    index = _manual_index([60, 60, 60, 60], seed=13)
    eng = HybridRetrievalEngine(index, cache_capacity=2, tile_len=128,
                                update_interval=1, transit_substages=2,
                                kernel_impl="ref")
    rng = np.random.default_rng(13)
    q = rng.standard_normal(index.dim).astype(np.float32)
    eng.search_substage([(q, 0, TopK.empty(3))])  # refresh -> 0 in transit
    assert not eng.cache.is_resident(0)
    for _ in range(2):
        eng.search_substage([])
    assert eng.cache.is_resident(0)


def test_delta_upload_instead_of_full_invalidation():
    """Cluster swaps must delta-update the device mirror, not rebuild it."""
    index = _manual_index([60, 60, 60, 60, 60], seed=11)
    eng = HybridRetrievalEngine(index, cache_capacity=4, tile_len=128,
                                update_interval=1, transit_substages=0,
                                kernel_impl="ref")
    rng = np.random.default_rng(11)
    q = rng.standard_normal((4, index.dim)).astype(np.float32)
    # phase 1: clusters {0, 1} become resident and are device-scanned
    for _ in range(3):
        eng.search_substage([(q[i], i % 2, TopK.empty(3)) for i in range(4)])
    assert eng.cache.stats.hits > 0
    assert eng.stats()["uploads"]["full"] == 1  # one initial mirror build
    # phase 2: cluster 4 gets hot, swaps in (capacity 4 forces an eviction)
    # -> later device scans ride a per-slot delta, never a full re-upload
    for _ in range(6):
        eng.search_substage([(q[i], 4, TopK.empty(3)) for i in range(4)])
    assert eng.cache.is_resident(4)
    up = eng.stats()["uploads"]
    assert up["full"] == 1  # never rebuilt from scratch
    assert up["delta"] >= 1 and up["delta_slots"] >= 1
    # device results after the delta match the host reference
    before = eng.stats()["device_items"]
    res = eng.search_substage([(q[0], 4, TopK.empty(3))])
    ref = index.search_cluster_batch([(q[0], 4, TopK.empty(3))])
    np.testing.assert_array_equal(res[0].ids, ref[0].ids)
    assert eng.stats()["device_items"] - before == 1
