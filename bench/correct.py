"""The comparison that decides ``correct``.

Three things are compared, on what the timed path produced:

* generation: a sample of served sequences, drawn from the seed with the
  longest in it.  The plain float32 reference (``bench.reference.lm``) runs
  once over each sequence's model input with its served tokens, and the
  number compared is the widest gap by which a served token's logit lies
  below the reference's best logit at that position.  The model input is
  the token array the engine handed its prefill program, as the generation
  adapter recorded it (with whatever truncation and padding the engine
  applied), followed by the served tokens; every input position is
  attended.
* retrieval: a sample of finished retrieval stages.  An exact scan of the
  clusters the stage searched must give the top-k the request received:
  every distance within the limit, and the same ids wherever the k-th and
  (k+1)-th exact distances are further apart than that.
* accounting: each request due in the window that the client saw finish is
  finished in the scheduler and no other; none of them failed to come.

Each limit sits in the configuration file under ``limits``, with the
readings it was set from in ``PERF.md``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import seeds


class RetrievalLog:
    """Records each retrieval stage as it finishes, without changing it."""

    def __init__(self, sched):
        self.rows: list = []
        self._orig = sched._finish_ret_stage
        sched._finish_ret_stage = self._observe

    def _observe(self, req, now):
        ret = req.ret
        self.rows.append({
            "request_id": req.request_id, "q": np.array(ret.query_vec, np.float32),
            "searched": sorted({int(c) for c in ret.searched}),
            "ids": np.array(ret.topk.ids), "dists": np.array(ret.topk.dists),
            "k": int(ret.k), "from_cache": bool(ret.answered_from_cache)})
        return self._orig(req, now)


def probe_skew(rows: list, n_clusters: int) -> float:
    """Share of the probes searched that landed on the most probed eighth
    of the clusters."""
    counts = np.zeros(n_clusters, np.int64)
    for r in rows:
        counts[r["searched"]] += 1
    top = np.sort(counts)[::-1][: max(1, n_clusters // 8)]
    return float(top.sum() / max(counts.sum(), 1))


@dataclasses.dataclass
class Sample:
    gen: list
    ret: list
    flat: np.ndarray
    ids: np.ndarray
    offsets: np.ndarray
    n_ret_stages: int
    n_from_cache: int
    unanswered: int
    skew: float


def take_sample(st, ret_log, seed: int, config: dict, unanswered: int) -> Sample:
    """Copy out what the checks need, so the program's state can be freed."""
    chk = config["check"]
    rng = seeds.rng(seed, seeds.SAMPLE)
    seqs = [lv for lv in st.gen.finished_sequences()
            if not lv.evicted and len(lv.seq.tokens) >= 2]

    def length(lv):
        return lv.model_in.shape[-1] + len(lv.seq.tokens) - 1

    chosen = []
    if seqs:
        longest = max(range(len(seqs)), key=lambda i: length(seqs[i]))
        order = [longest] + [int(i) for i in rng.permutation(len(seqs)) if i != longest]
        total = 0
        for i in order:
            lv = seqs[i]
            toks = np.asarray(lv.seq.tokens, np.int32)
            served_in = np.asarray(lv.model_in, np.int32).reshape(-1)
            width = served_in.size
            chosen.append({"tokens": np.concatenate([served_in, toks[:-1]]),
                           "served": toks,
                           "positions": np.arange(width - 1, width - 1 + toks.size),
                           "request_id": lv.request_id})
            total += toks.size
            if total >= int(chk["gen_tokens"]):
                break
    rows = [r for r in ret_log.rows if not r["from_cache"] and r["searched"]]
    pick = rng.permutation(len(rows))[: int(chk["ret_stages"])]
    idx = st.index
    return Sample(gen=chosen, ret=[rows[i] for i in sorted(pick)], flat=idx.flat,
                  ids=idx.ids, offsets=idx.offsets, n_ret_stages=len(ret_log.rows),
                  n_from_cache=sum(r["from_cache"] for r in ret_log.rows),
                  unanswered=unanswered,
                  skew=probe_skew(ret_log.rows, idx.n_clusters))


def gen_gaps(sample: Sample, m: dict, seed: int, precision: str = "f32") -> dict:
    """Widest gap of a served token's reference logit below the reference's
    best, over the sample; with ``precision="fp8"`` the gap of the token the
    fp8 control puts first instead (the control's reading)."""
    from bench.reference import lm

    seqs = [{"tokens": s["tokens"], "positions": s["positions"],
             "cands": s["served"][:, None]} for s in sample.gen]
    if precision != "f32":
        ctrl = lm.score(m, seed, seqs, precision=precision)
        for s, c in zip(seqs, ctrl):
            s["cands"] = c["argmax"][:, None]
    ref = lm.score(m, seed, seqs)
    gaps = np.concatenate([r["best"] - r["picked"][:, 0] for r in ref]) if ref else np.zeros(0)
    return {"max_gap": float(gaps.max()) if gaps.size else 0.0,
            "tokens": int(gaps.size), "sequences": len(seqs),
            "share_not_best": float((gaps > 0).mean()) if gaps.size else 0.0}


def exact_topk(sample: Sample, row: dict, k: int, scan_dtype=None):
    """Squared distances of the stage's query to every row of the clusters
    it searched, in float64 from the stored float32 vectors (or from both
    rounded to ``scan_dtype``: the control); the k+1 smallest."""
    q = row["q"] if scan_dtype is None else row["q"].astype(scan_dtype)
    q = q.astype(np.float64)
    d_all, i_all = [], []
    for c in row["searched"]:
        lo, hi = int(sample.offsets[c]), int(sample.offsets[c + 1])
        x = sample.flat[lo:hi]
        if scan_dtype is not None:
            x = x.astype(scan_dtype)
        diff = x.astype(np.float64) - q
        d_all.append((diff * diff).sum(1))
        i_all.append(sample.ids[lo:hi])
    d = np.concatenate(d_all)
    i = np.concatenate(i_all)
    o = np.argsort(d, kind="stable")[: k + 1]
    return d[o], i[o]


def ret_errors(sample: Sample, tol: float, control_dtype=None) -> dict:
    """Worst distance error and id mismatches of the served top-k (or, with
    ``control_dtype``, of the exact scan computed at that precision) against
    the exact scan."""
    worst, mismatched = 0.0, 0
    for row in sample.ret:
        k = row["k"]
        d_ref, i_ref = exact_topk(sample, row, k)
        if control_dtype is None:
            ids, dists = row["ids"], row["dists"]
        else:
            dists, ids = exact_topk(sample, row, k, control_dtype)
            dists, ids = dists[:k], ids[:k]
        n = min(int((ids >= 0).sum()), len(d_ref))
        if n:
            worst = max(worst, float(np.abs(dists[:n] - d_ref[:n]).max()))
        clear = len(d_ref) <= k or d_ref[k] - d_ref[k - 1] > tol
        if clear and set(ids[ids >= 0].tolist()) != set(i_ref[:k].tolist()):
            mismatched += 1
    return {"max_dist_err": worst, "mismatched": mismatched, "stages": len(sample.ret)}


def controls(sample: Sample, config: dict, seed: int) -> dict:
    """The controls' readings on the same sample: the reference computed a
    precision below the configuration's, in the program's place (fp8 for
    the bf16 model, bfloat16 for the float32 index)."""
    import ml_dtypes

    from bench.stack import model_dict

    g = gen_gaps(sample, model_dict(config), seed, precision="fp8")
    r = ret_errors(sample, float(config["limits"]["ret_dist_err"]),
                   control_dtype=ml_dtypes.bfloat16)
    return {"gen_logit_gap": g["max_gap"], "ret_dist_err": r["max_dist_err"],
            "ret_ids_mismatched": r["mismatched"]}


def check(sample: Sample, config: dict, seed: int, accounting: int) -> list:
    from bench.stack import model_dict

    lim = config["limits"]
    g = gen_gaps(sample, model_dict(config), seed)
    r = ret_errors(sample, float(lim["ret_dist_err"]))

    def row(name, value, limit, detail):
        return {"name": name, "value": value, "limit": limit,
                "ok": value <= limit, "detail": detail}

    return [
        row("gen_logit_gap", g["max_gap"], float(lim["gen_logit_gap"]),
            f"over {g['tokens']} served tokens of {g['sequences']} sequences; "
            f"{g['share_not_best']:.4f} of them not the reference's best"),
        row("ret_dist_err", r["max_dist_err"], float(lim["ret_dist_err"]),
            f"over {r['stages']} of {sample.n_ret_stages} retrieval stages "
            f"({sample.n_from_cache} answered from the request's cache, not compared)"),
        row("ret_ids_mismatched", r["mismatched"], 0, "stages whose top-k ids differ"),
        row("accounting_mismatch", accounting, 0,
            "window requests whose client and scheduler disagree on finishing"),
        row("unanswered", sample.unanswered, 0,
            "window requests not finished when following stopped"),
    ]
