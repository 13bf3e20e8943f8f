"""Data and traffic from ``--seed``: the corpus, the query vectors, the
arrival schedule and the write stream of one run.

The embeddings are a mixture of clusters in a ``latent``-D space lifted to
the configuration's width by a random map, plus a little noise:
embeddings of text or images lie near such a low-dimensional structure,
and isotropic clusters in the full width do not (there every point of a
cluster sits at almost the same distance from a query, so the exact top-k
is a near-tie that no index reproduces). Queries are draws from the same
mixture, never corpus points.

The dataset is the configuration's: the mixture, the docs and the pool of
queries are drawn once from its ``geometry_seed``, so every ``--seed``
serves the same docs and asks the same queries. ``--seed`` draws the order
the docs are written in (within each cluster, for a clustered runbook),
the order the queries come in, and the order of the arrival gaps: every
seed offers the same work in another order, so seeds move a metric only as
far as order does.

Arrivals are an open loop: a fixed multiset of exponential inter-arrival
gaps (the quantiles of the exponential at the mix's rate), shuffled by the
seed. With ``{"kind": "on_off", "on_s": a, "off_s": b}`` the queries come
only in the on periods, at the rate that keeps the window's mean at the
mix's rate.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SEED_MOD = 2**63 - 1


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % SEED_MOD, *stream])


@dataclasses.dataclass(frozen=True)
class Mixture:
    centers: np.ndarray  # (C, latent)
    lift: np.ndarray  # (latent, dim)
    noise: float

    def draw(self, rng: np.random.Generator, clusters: np.ndarray) -> np.ndarray:
        z = self.centers[clusters] + rng.standard_normal(
            (len(clusters), self.centers.shape[1]))
        x = z @ self.lift + self.noise * rng.standard_normal(
            (len(clusters), self.lift.shape[1]))
        return x.astype(np.float32)


def mixture(dim: int, data: dict, spread: float = 2.0,
            noise: float = 0.05) -> Mixture:
    """The dataset's distribution, from the configuration's ``data``."""
    latent, clusters = data["latent"], data["clusters"]
    rng = _rng(data["geometry_seed"], 0)
    centers = spread * rng.standard_normal((clusters, latent))
    lift = rng.standard_normal((latent, dim)) / np.sqrt(latent)
    return Mixture(centers, lift, noise)


@dataclasses.dataclass
class Corpus:
    """Docs in the order they are written: position ``i`` holds doc id
    ``ids[i]``. The first ``n_load`` are loaded in set-up; the rest feed
    the write stream."""
    ids: np.ndarray  # (N,) int64
    vectors: np.ndarray  # (N, dim) float32
    clusters: np.ndarray  # (N,) int
    n_load: int


def make_corpus(seed: int, dim: int, data: dict, n_load: int, n_stream: int,
                cluster_order: bool) -> Corpus:
    """The dataset's ``n_load + n_stream`` docs, in the order ``seed``
    writes them. With ``cluster_order`` docs are written cluster by
    cluster, in cluster order (the streaming track's clustered runbook):
    which docs are loaded is fixed, and the seed orders the docs inside
    each cluster's loaded and streamed parts; otherwise it orders them
    all."""
    mix = mixture(dim, data)
    n = n_load + n_stream
    rng = _rng(data["geometry_seed"], 1)
    assign = rng.integers(0, data["clusters"], n)
    vecs = mix.draw(rng, assign)
    order = _rng(seed, 1).permutation(n)
    if cluster_order:
        streamed = np.zeros(n, bool)
        streamed[np.argsort(assign, kind="stable")[n_load:]] = True
        order = order[np.lexsort((streamed[order], assign[order]))]
    return Corpus(ids=np.arange(n, dtype=np.int64), vectors=vecs[order],
                  clusters=assign[order], n_load=n_load)


def make_queries(seed: int, dim: int, data: dict, n: int,
                 clusters: np.ndarray | None = None,
                 stream: int = 2) -> np.ndarray:
    """The dataset's ``n`` queries, from ``clusters`` when given (uniformly
    among them), else from the whole mixture, in the order ``seed``
    asks them."""
    mix = mixture(dim, data)
    rng = _rng(data["geometry_seed"], stream)
    pool = (np.arange(data["clusters"]) if clusters is None
            else np.asarray(clusters))
    q = mix.draw(rng, pool[rng.integers(0, len(pool), n)])
    return q[_rng(seed, stream).permutation(n)]


def _active(arrivals: dict | None, seconds: float) -> list[tuple[float, float]]:
    """The parts of the window in which queries arrive."""
    kind = (arrivals or {}).get("kind", "poisson")
    if kind == "poisson":
        return [(0.0, seconds)]
    if kind == "on_off":
        on, off = float(arrivals["on_s"]), float(arrivals["off_s"])
        if on <= 0 or off < 0:
            raise ValueError(f"on_off arrivals need on_s > 0, off_s >= 0: "
                             f"{arrivals}")
        starts = np.arange(0.0, seconds, on + off)
        return [(float(t), min(float(t) + on, seconds)) for t in starts]
    raise ValueError(f"unknown arrivals kind {kind!r}")


def arrival_offsets(seed: int, rate_qps: float, seconds: float,
                    arrivals: dict | None = None) -> np.ndarray:
    """Due times (s from the window's start) of an open loop that offers
    ``rate_qps`` on average over the window: a fixed multiset of
    exponential gaps in seeded order, laid over the ``arrivals``' on
    periods (all of the window for Poisson)."""
    n = int(round(rate_qps * seconds))
    if n <= 0:
        return np.zeros(0)
    spans = _active(arrivals, seconds)
    active = sum(e - s for s, e in spans)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= active * (n - 0.5) / n / gaps.sum()  # last one due inside
    tau = np.cumsum(_rng(seed, 3).permutation(gaps))  # on-period time
    ends = np.cumsum([e - s for s, e in spans])  # on-period time
    k = np.minimum(np.searchsorted(ends, tau, side="right"), len(spans) - 1)
    starts = np.array([s for s, _ in spans])
    return starts[k] + tau - np.r_[0.0, ends[:-1]][k]


def live_query_clusters(corpus: Corpus, keep_from: int) -> np.ndarray:
    """Clusters every one of whose loaded docs sits at a position at or
    after ``keep_from``: the write stream deletes from the oldest end, so
    these stay live through the window."""
    first = corpus.clusters[:corpus.n_load]
    old = set(first[:keep_from].tolist())
    return np.asarray(sorted(set(first.tolist()) - old))
