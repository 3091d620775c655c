"""IVF index, k-means, hot cache, hybrid engine."""
import numpy as np
import pytest

from repro.retrieval import (
    ClusterCostModel,
    HotClusterCache,
    HybridRetrievalEngine,
    IVFIndex,
    TopK,
    plan_memory_split,
)


def test_kmeans_assignment_is_argmin(small_corpus):
    import jax

    from repro.retrieval.kmeans import assign_clusters, kmeans

    docs, _, _ = small_corpus
    cent, asn = kmeans(jax.random.PRNGKey(0), docs[:4000], 16, iters=3)
    cent, asn = np.asarray(cent), np.asarray(asn)
    d = ((docs[:4000, None, :] - cent[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(asn, d.argmin(1))


def test_ivf_recall_vs_bruteforce(small_index, small_corpus):
    docs, _, _ = small_corpus
    rng = np.random.default_rng(1)
    q = docs[rng.choice(len(docs), 24)] + 0.03 * rng.standard_normal((24, docs.shape[1])).astype(np.float32)
    D, I = small_index.search(q, nprobe=12, k=10)
    bf = (q**2).sum(-1, keepdims=True) - 2 * q @ docs.T + (docs**2).sum(-1)[None]
    bf_top = np.argsort(bf, axis=1)[:, :10]
    recall = np.mean([len(set(I[i]) & set(bf_top[i])) / 10 for i in range(24)])
    assert recall > 0.55, f"recall {recall}"
    # full-probe search == brute force
    D2, I2 = small_index.search(q[:4], nprobe=small_index.n_clusters, k=5)
    np.testing.assert_array_equal(I2, bf_top[:4, :5])


def test_ivf_full_probe_distances_sorted(small_index, small_corpus):
    docs, _, _ = small_corpus
    D, I = small_index.search(docs[:3], nprobe=8, k=6)
    assert np.all(np.diff(D, axis=1) >= -1e-6)
    assert np.all(I >= 0)


def test_topk_merge_properties():
    rng = np.random.default_rng(2)
    tk = TopK.empty(5)
    seen = {}
    for _ in range(6):
        d = rng.random(7).astype(np.float32)
        ids = rng.choice(10_000, 7, replace=False)
        for dist, i in zip(d, ids):
            seen[i] = min(dist, seen.get(i, np.inf))
        tk = tk.merge(d, ids)
    expect = sorted(seen.items(), key=lambda kv: kv[1])[:5]
    np.testing.assert_allclose(tk.dists, [v for _, v in expect], rtol=1e-6)
    assert list(tk.ids) == [k for k, _ in expect]


def test_doc_cluster_roundtrip(small_index):
    rng = np.random.default_rng(3)
    docs = rng.choice(small_index.ids, 64, replace=False)
    cl = small_index.doc_cluster(docs)
    for d, c in zip(docs, cl):
        lo, hi = small_index.offsets[c], small_index.offsets[c + 1]
        assert d in small_index.ids[lo:hi]


def test_cluster_tensor_padding(small_index):
    cids = [0, 1, 5]
    slab, valid, ids = small_index.cluster_tensor(cids, pad_to=128)
    assert slab.shape[1] % 128 == 0
    for j, c in enumerate(cids):
        assert valid[j] == small_index.cluster_size(c)
        assert (ids[j, valid[j]:] == -1).all()
        np.testing.assert_array_equal(
            slab[j, : valid[j]],
            small_index.flat[small_index.offsets[c]: small_index.offsets[c + 1]],
        )


def test_hot_cache_transit_and_update():
    cache = HotClusterCache(32, capacity=4, update_interval=2, transit_substages=2)
    for _ in range(6):
        for c in [1, 2, 3, 4]:
            cache.lookup(c)
        cache.end_substage()
    # after updates, hot clusters become resident (transit respected)
    assert set(cache.resident_ids) <= {1, 2, 3, 4}
    assert len(cache.resident_ids) > 0
    assert cache.stats.swaps >= 4
    # cold cluster never resident
    assert not cache.is_resident(31)


def test_hot_cache_adapts_to_shift():
    cache = HotClusterCache(16, capacity=2, update_interval=2,
                            transit_substages=0, decay=0.5)
    for _ in range(8):
        cache.lookup(0); cache.lookup(1); cache.end_substage()
    assert set(cache.resident_ids) == {0, 1}
    for _ in range(16):
        cache.lookup(7); cache.lookup(9); cache.end_substage()
    assert set(cache.resident_ids) == {7, 9}


def test_eq2_memory_split():
    # generation throughput saturates at 2 GB KV; retrieval constant
    t_gen = lambda kv, rps: min(kv / 1e9, 2.0)
    t_ret = lambda rps: 1.5
    kv, cache = plan_memory_split(
        4_000_000_000, t_gen=t_gen, t_ret=t_ret, rps_g=1, rps_r=1,
        kv_candidates=[1_000_000_000, 1_500_000_000, 2_000_000_000, 3_000_000_000],
    )
    assert kv == 1_500_000_000  # smallest KV whose T_G >= T_R
    assert cache == 4_000_000_000 - kv


def test_hybrid_engine_matches_host_path(small_index, small_corpus):
    docs, _, _ = small_corpus
    rng = np.random.default_rng(4)
    q = docs[rng.choice(len(docs), 8)]
    eng = HybridRetrievalEngine(small_index, cache_capacity=8,
                                update_interval=1, transit_substages=0,
                                kernel_impl="ref")
    # warm the cache on some clusters
    probes = small_index.probe_order(q, 4)
    for _ in range(4):
        work = [(q[i], int(probes[i, j]), TopK.empty(5))
                for i in range(8) for j in range(2)]
        res = eng.search_substage(work)
    # device-path results must equal the host path exactly
    work = [(q[i], int(probes[i, 0]), TopK.empty(5)) for i in range(8)]
    before = eng.stats()["device_items"]
    res = eng.search_substage(work)
    ref = small_index.search_cluster_batch(
        [(q[i], int(probes[i, 0]), TopK.empty(5)) for i in range(8)])
    for r, rr in zip(res, ref):
        np.testing.assert_array_equal(r.ids, rr.ids)
        np.testing.assert_allclose(r.dists, rr.dists, rtol=1e-4, atol=1e-5)
    assert eng.stats()["device_items"] > before  # cache actually used


def test_cost_model_monotone(small_index):
    cm = ClusterCostModel.calibrate(small_index, n_samples=8)
    assert cm.per_vector_us > 0
    assert cm.cost_us(1000) > cm.cost_us(10)
