"""The retrieval deployment made from the seed: corpus, IVF index, queries.

The corpus is made on the device in one jitted program: unit-norm topic
vectors, documents drawn from Zipf-popular topics with noise around them,
k-means on a sample, assignment of every document, and the cluster-sorted
layout the program's ``IVFIndex`` holds.  Only the finished index crosses
to the host.

Noise is given at a reference width and scaled by ``sqrt(ref_dim / dim)``
per coordinate, so the cosine of a document or a query to its topic is the
same at every width: unscaled per-coordinate noise at 1024 dimensions would
bury the topic and with it the cluster skew the hot cache depends on.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.retrieval.ivf import IVFIndex

from bench import seeds


def noise_scale(r: dict, noise: float) -> float:
    return noise * math.sqrt(r["noise_ref_dim"] / r["dim"])


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _assign(x, cent, chunk):
    """Nearest centroid of each row and the squared distance to it, in
    chunks of rows."""
    c2 = (cent * cent).sum(-1)

    def one(xc):
        d2 = c2[None, :] - 2.0 * jnp.dot(xc, cent.T,
                                         precision=jax.lax.Precision.HIGHEST)
        a = jnp.argmin(d2, axis=-1).astype(jnp.int32)
        return a, jnp.min(d2, axis=-1) + (xc * xc).sum(-1)

    n, d = x.shape
    pad = -n % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    a, m = jax.lax.map(one, xp.reshape(-1, chunk, d))
    return a.reshape(-1)[:n], m.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("n_docs", "dim", "n_topics", "n_clusters",
                                   "n_sample", "iters", "chunk"))
def _build(key, *, n_docs, dim, n_topics, n_clusters, n_sample, iters, chunk,
           zipf_alpha, doc_noise):
    k_top, k_doc, k_noise, k_init = jax.random.split(key, 4)
    topics = _unit(jax.random.normal(k_top, (n_topics, dim), jnp.float32))
    pops = jnp.arange(1, n_topics + 1, dtype=jnp.float32) ** -zipf_alpha
    cdf = jnp.cumsum(pops / pops.sum())
    doc_topic = jnp.minimum(
        jnp.searchsorted(cdf, jax.random.uniform(k_doc, (n_docs,))), n_topics - 1)
    docs = _unit(topics[doc_topic]
                 + doc_noise * jax.random.normal(k_noise, (n_docs, dim),
                                                 jnp.float32))
    sample = docs[:n_sample]
    init = sample[jax.random.choice(k_init, n_sample, (n_clusters,),
                                    replace=False)]

    def lloyd(_, cent):
        a, _ = _assign(sample, cent, chunk)
        sums = jax.ops.segment_sum(sample, a, num_segments=n_clusters)
        cnt = jax.ops.segment_sum(jnp.ones((n_sample,), jnp.float32), a,
                                  num_segments=n_clusters)
        return jnp.where((cnt > 0)[:, None], sums / jnp.maximum(cnt, 1.0)[:, None],
                         cent)

    cent = jax.lax.fori_loop(0, iters, lloyd, init)
    asn, member2 = _assign(docs, cent, chunk)
    order = jnp.argsort(asn, stable=True)
    flat = docs[order]
    counts = jnp.bincount(asn, length=n_clusters)
    radii = jax.ops.segment_max(jnp.sqrt(jnp.maximum(member2, 0.0)), asn,
                                num_segments=n_clusters)
    return (topics, cent, flat, (flat * flat).sum(-1), order, counts,
            jnp.maximum(radii, 0.0))


def build_index(r: dict, seed: int) -> tuple[IVFIndex, np.ndarray]:
    """(IVFIndex, topic vectors) of the configuration's retrieval deployment."""
    n = int(r["corpus_docs"])
    chunk = 8192
    out = _build(seeds.key(seed, seeds.CORPUS),
                 n_docs=n, dim=int(r["dim"]), n_topics=int(r["n_topics"]),
                 n_clusters=int(r["ivf_nlist"]),
                 n_sample=min(n, int(r["kmeans_sample"])),
                 iters=int(r["kmeans_iters"]), chunk=chunk,
                 zipf_alpha=float(r["zipf_alpha"]),
                 doc_noise=noise_scale(r, float(r["doc_noise"])))
    topics, cent, flat, norms, order, counts, radii = (np.asarray(a) for a in out)
    del out
    offsets = np.zeros(len(counts) + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    index = IVFIndex(centroids=cent, flat=flat, flat_norms=norms,
                     ids=order.astype(np.int64), offsets=offsets, radii=radii)
    return index, topics


@dataclasses.dataclass
class QueryEmbedder:
    """Per-request query embeddings around a Zipf-popular topic, with the
    noise scaled to the width (the ``Embedder`` protocol of
    ``repro.retrieval.synthetic``).  Round ``r`` of a request walks
    ``inter_drift`` away from round ``r - 1``; a partial generation's
    embedding approaches the final one as its prefix grows."""

    topics: np.ndarray
    zipf_alpha: float
    query_noise: float  # per coordinate, already scaled to the width
    inter_drift: float
    partial_noise: float
    seed: int

    def __post_init__(self):
        self.dim = int(self.topics.shape[1])
        pops = np.arange(1, len(self.topics) + 1, dtype=np.float64) ** -self.zipf_alpha
        self._pops = pops / pops.sum()

    def _rng(self, request_id: int, tag: int) -> np.random.Generator:
        return seeds.rng(self.seed, seeds.QUERIES, request_id, tag)

    def request_topic(self, request_id: int) -> int:
        return int(self._rng(request_id, 0).choice(len(self._pops), p=self._pops))

    def embed_query(self, request_id: int, round_idx: int) -> np.ndarray:
        base = self.topics[self.request_topic(request_id)].astype(np.float64)
        anchor = base + self.query_noise * self._rng(request_id, 1).standard_normal(self.dim)
        walk = np.zeros(self.dim)
        for r in range(1, round_idx + 1):
            step = self._rng(request_id, 100 + r).standard_normal(self.dim)
            walk += self.inter_drift * step / math.sqrt(self.dim) * np.linalg.norm(anchor)
        v = anchor + walk
        return (v / np.linalg.norm(v)).astype(np.float32)

    def embed_partial(self, request_id: int, round_idx: int, ratio: float) -> np.ndarray:
        final = self.embed_query(request_id, round_idx).astype(np.float64)
        resid = self._rng(request_id, 200 + round_idx).standard_normal(self.dim)
        amp = self.partial_noise * (1.0 - min(max(ratio, 0.0), 1.0)) ** 1.5
        v = final + amp * resid / math.sqrt(self.dim)
        return (v / np.linalg.norm(v)).astype(np.float32)


def make_embedder(r: dict, topics: np.ndarray, seed: int) -> QueryEmbedder:
    return QueryEmbedder(topics, zipf_alpha=float(r["zipf_alpha"]),
                         query_noise=noise_scale(r, float(r["query_noise"])),
                         inter_drift=float(r["inter_drift"]),
                         partial_noise=float(r["partial_noise"]), seed=seed)
