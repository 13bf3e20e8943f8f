"""The plain reference and the comparison that decides ``correct``.

The reference is exact k-nearest-neighbour search in float64 numpy over
the corpus positions that were live when each query's micro-batch was
dispatched. It imports nothing of the program and takes nothing the
program made: it reads the corpus the harness generated from the seed.

The numbers compared, each with its limit:

* ``dist_gap``: over every answer of the window, the largest gap between
  the distance the program reported for a returned doc and that doc's
  exact distance, as a share of the query's exact k-th nearest distance.
  Its limit is set from readings (``PERF.md``): sound runs of the program
  read ~1e-7; the control (the reference with the vectors in bfloat16)
  reads ~1e-2.
* ``recall_miss``: 1 - recall@k of every answer of the window against
  the reference: the share of the exact top-k the graph search missed.
  Its limit is the cell's (``limits/<cell>.json``), set from readings
  (``PERF.md``): sound runs of the program, and the same program with its
  full-precision rerank cut to the k best by ADC (a coarser ranking).
* ``bad_answers``: answers holding a doc that was not live at dispatch
  (deleted or not yet written), one the query's filter excludes, a
  duplicate, a missing id, or distances out of order. Exact: limit 0.
* ``unanswered``: window queries with no complete 200 answer. Limit 0.
* ``readback_missing``: acknowledged upserts, a sample from the seed,
  that the service's exact plan, queried by their own vector, does not
  return. Limit 0.
* ``deleted_returned``: deleted docs, a sample from the seed, that the
  exact plan returns for their own vector. Limit 0.

Recall@k is also an end-to-end metric with its bound: the bound catches a
small loss, the limit a search that no longer finds the neighbours. The
control scores higher on recall than the program; it fails ``dist_gap``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

DIST_GAP_LIMIT = 1e-4  # see PERF.md for the readings it was set from


@dataclasses.dataclass
class Reference:
    truth: np.ndarray  # (n, k) positions of the exact top-k
    kth: np.ndarray  # (n,) exact k-th nearest distance


def exact_topk(queries: np.ndarray, corpus: np.ndarray, live_lo: np.ndarray,
               live_hi: np.ndarray, k: int, block: int = 256,
               eligible: np.ndarray | None = None) -> Reference:
    """Exact L2 top-k (corpus positions) of each query over positions
    ``[live_lo[i], live_hi[i])``, in float64, in blocks of queries;
    ``eligible`` (one bool per corpus position) keeps only the positions
    the queries' filter admits."""
    n = len(queries)
    lo_all, hi_all = int(live_lo.min(initial=0)), int(live_hi.max(initial=0))
    x = corpus[lo_all:hi_all].astype(np.float64)
    xx = (x * x).sum(1)
    pos = np.arange(lo_all, hi_all)
    barred = (np.zeros(len(pos), bool) if eligible is None
              else ~np.asarray(eligible, bool)[lo_all:hi_all])
    truth = np.full((n, k), -1, np.int64)
    kth = np.zeros(n)
    for b in range(0, n, block):
        q = queries[b:b + block].astype(np.float64)
        d = (q * q).sum(1)[:, None] - 2.0 * q @ x.T + xx[None, :]
        dead = ((pos[None, :] < live_lo[b:b + block, None])
                | (pos[None, :] >= live_hi[b:b + block, None])
                | barred[None, :])
        d[dead] = np.inf
        part = np.argpartition(d, k - 1, axis=1)[:, :k]
        dk = np.take_along_axis(d, part, 1)
        order = np.argsort(dk, axis=1)
        truth[b:b + block] = pos[np.take_along_axis(part, order, 1)]
        # the expanded form loses digits; the k-th distance is recomputed
        # directly from the vectors
        far = pos[part[np.arange(len(q)), order[:, -1]]]
        diff = q - corpus[far].astype(np.float64)
        kth[b:b + block] = (diff * diff).sum(1)
    return Reference(truth, kth)


def recall(found: np.ndarray, truth: np.ndarray) -> float:
    """Mean share of each query's exact top-k that the answer holds."""
    k = truth.shape[1]
    hits = sum(len(np.intersect1d(f[f >= 0], t)) for f, t in zip(found, truth))
    return hits / (len(truth) * k)


def dist_gap(queries, corpus, pos, dists, kth) -> float:
    """Largest |reported − exact| distance of a returned doc, over the
    query's exact k-th nearest distance. ``pos`` are corpus positions, -1
    where no doc was returned (left out here; ``bad_answers`` counts it)."""
    gap = 0.0
    for b in range(0, len(queries), 1024):
        p = pos[b:b + 1024]
        ok = p >= 0
        if not ok.any():
            continue
        q = queries[b:b + 1024].astype(np.float64)
        x = corpus[np.maximum(p, 0)].astype(np.float64)
        exact = ((q[:, None, :] - x) ** 2).sum(-1)
        rel = np.abs(dists[b:b + 1024].astype(np.float64) - exact)
        rel /= np.maximum(kth[b:b + 1024], 1e-30)[:, None]
        gap = max(gap, float(rel[ok].max(initial=0.0)))
    return gap


def bad_answers(pos, dists, live_lo, live_hi, eligible=None) -> int:
    """Answers holding a doc not live at dispatch or not ``eligible``, a
    duplicate, a missing id, or distances out of order."""
    missing = (pos < 0).any(1)
    dead = ((pos < live_lo[:, None]) | (pos >= live_hi[:, None])).any(1)
    if eligible is not None:
        barred = ~np.asarray(eligible, bool)[np.maximum(pos, 0)]
        dead |= (barred & (pos >= 0)).any(1)
    s = np.sort(pos, axis=1)
    dup = (s[:, 1:] == s[:, :-1]).any(1)
    d = dists.astype(np.float64)
    unordered = (d[:, 1:] < d[:, :-1]).any(1)
    return int((missing | dead | dup | unordered).sum())


@dataclasses.dataclass
class Check:
    numbers: dict  # name -> (value, limit)
    recall: float

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.numbers.values())


def compare(queries, corpus, pos, win_dists, status, complete, live_lo,
            live_hi, k, recall_miss_limit: float, readback=None,
            eligible=None) -> Check:
    """``pos`` holds the corpus positions of the returned docs (-1 where
    unknown); ``readback`` is (acknowledged positions, their answers'
    positions, deleted positions, their answers' positions);
    ``eligible`` marks the positions the queries' filter admits."""
    ref = exact_topk(queries, corpus, live_lo, live_hi, k, eligible=eligible)
    answered = (status == 200) & complete
    rec = recall(pos, ref.truth)
    nums = {
        "dist_gap": (dist_gap(queries, corpus, pos, win_dists, ref.kth),
                     DIST_GAP_LIMIT),
        "recall_miss": (1.0 - rec, float(recall_miss_limit)),
        "bad_answers": (bad_answers(pos[answered], win_dists[answered],
                                    live_lo[answered], live_hi[answered],
                                    eligible), 0),
        "unanswered": (int((~answered).sum()), 0),
    }
    if readback is not None:
        acked, acked_found, gone, gone_found = readback
        nums["readback_missing"] = (
            int(sum(p not in row for p, row in zip(acked, acked_found))), 0)
        nums["deleted_returned"] = (
            int(sum(p in row for p, row in zip(gone, gone_found))), 0)
    return Check(nums, rec)


def bf16_answers(queries, corpus, live_lo, live_hi, k, block: int = 512,
                 eligible=None):
    """The control: the reference put in the program's place, with the
    vectors and queries held in bfloat16 (the storage a later change would
    be tempted to use) and distances accumulated in float32 on the device.
    Returns (positions, distances)."""
    import jax
    import jax.numpy as jnp

    lo_all, hi_all = int(live_lo.min()), int(live_hi.max())
    x = jnp.asarray(corpus[lo_all:hi_all], jnp.bfloat16)
    xx = jnp.sum(jnp.square(x.astype(jnp.float32)), 1)
    posv = jnp.arange(lo_all, hi_all)
    ok = jnp.asarray(np.ones(hi_all - lo_all, bool) if eligible is None
                     else np.asarray(eligible, bool)[lo_all:hi_all])

    @jax.jit
    def one(q, lo, hi, x, xx, posv, ok):
        q = q.astype(jnp.bfloat16)
        qf = q.astype(jnp.float32)
        d = (jnp.sum(qf * qf, 1)[:, None]
             - 2.0 * jnp.dot(q, x.T, preferred_element_type=jnp.float32)
             + xx[None, :])
        d = jnp.where((posv[None, :] >= lo[:, None])
                      & (posv[None, :] < hi[:, None]) & ok[None, :], d,
                      jnp.inf)
        neg, idx = jax.lax.top_k(-d, k)
        return posv[idx], -neg

    out_p, out_d = [], []
    for b in range(0, len(queries), block):
        q = np.zeros((block, queries.shape[1]), np.float32)
        m = len(queries[b:b + block])
        q[:m] = queries[b:b + block]
        lo = np.zeros(block, np.int64)
        hi = np.full(block, hi_all, np.int64)
        lo[:m], hi[:m] = live_lo[b:b + block], live_hi[b:b + block]
        p, d = one(q, lo, hi, x, xx, posv, ok)
        out_p.append(np.asarray(p)[:m])
        out_d.append(np.asarray(d)[:m])
    return np.concatenate(out_p), np.concatenate(out_d)
