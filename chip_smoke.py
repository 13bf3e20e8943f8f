"""Bring-up smoke for the served vector-search path on a TPU.

    python chip_smoke.py             # one chip: load, query, filter, guarantees, kernels
    python chip_smoke.py --chips 4   # four chips: spmd fan-out vs serial dispatch

One process, one deployment: the paper's §4 single partition
(``configs/cosmosann.py:config()``: 768-D float32 L2, PQ M=96 × K=256,
R=32, L=100, k=10, beam width 4, 4 replicas), loaded with clustered
synthetic vectors made from ``--seed`` through ``VectorCollectionService``
(Bw-Tree terms + WAL), then queried through the serving engine's
micro-batcher. Every check compares against a plain-numpy reference on the
host. Any failed check raises; the last stdout line is a JSON object naming
the device, printed only when every phase passed.

Times are host wall seconds around work that ends on the device; compile
seconds (XLA backend compiles observed through ``jax.monitoring``) are
printed beside them. Without a TPU the script exits non-zero and runs
nothing.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

RECALL_FLOOR = 0.85
N_QUERIES = 256
N_NEW = 16  # docs upserted by the guarantee phase
CATEGORIES = 10  # filter field cardinality (10% selectivity per value)
FULL_PARTITION = 100_000  # VectorCollectionService's max_vectors_per_partition
# --chips 4 checks placement and id identity, which do not depend on the
# partition size, at four times the chip cost per second: it loads less
SPMD_PARTITION = 10_000
REDUCED = ("reduced: 100,000 vectors per partition (one full default "
           "partition), not the paper's 1M/10M: the incremental build path "
           "(host-side reverse-edge merge, graph arrays re-uploaded per "
           "write batch; ROADMAP reach item 2) cannot load more within one "
           "run")
REDUCED_SPMD = ("reduced: 4 partitions x 10,000 vectors, not 4 x 100,000: "
                "the host loads partitions one after another, and placement "
                "and id identity do not depend on the partition size")


class SmokeFailure(AssertionError):
    """A phase's output disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileTimer:
    """Sums XLA backend compile seconds reported through jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_s: float, **_kw) -> None:
        if event == self.EVENT:
            self.total_s += duration_s


# ---------------------------------------------------------------------------
# data and deployment
# ---------------------------------------------------------------------------


LATENT = 16  # intrinsic dimension of the synthetic embeddings
N_CLUSTERS = 64


def _mixture(seed: int, dim: int) -> tuple[np.ndarray, np.ndarray]:
    """Cluster centres in a ``LATENT``-D space and the fixed random map that
    lifts it to ``dim``: embeddings of text or images lie near such a
    low-dimensional structure. Isotropic clusters in the full ``dim`` do
    not: there every point of a cluster sits at almost the same distance
    from a query, so the exact top-k beyond the nearest is a near-tie that
    no index can (or needs to) reproduce."""
    rng = np.random.default_rng(seed)
    centers = 2.0 * rng.standard_normal((N_CLUSTERS, LATENT))
    lift = rng.standard_normal((LATENT, dim)) / np.sqrt(LATENT)
    return centers, lift


def _draw(rng, centers, lift, n: int) -> tuple[np.ndarray, np.ndarray]:
    assign = rng.integers(0, len(centers), n)
    z = centers[assign] + rng.standard_normal((n, centers.shape[1]))
    noise = 0.05 * rng.standard_normal((n, lift.shape[1]))
    return (z @ lift + noise).astype(np.float32), assign


def make_corpus(seed: int, n: int, dim: int) -> tuple[np.ndarray, np.ndarray]:
    """Clustered vectors and their cluster ids, in bulk from ``seed``."""
    return _draw(np.random.default_rng([seed, 1]), *_mixture(seed, dim), n)


def make_queries(seed: int, n: int, dim: int) -> np.ndarray:
    """Fresh draws from the corpus's distribution (not corpus points)."""
    return _draw(np.random.default_rng([seed, 2]), *_mixture(seed, dim), n)[0]


def exact_topk(queries: np.ndarray, corpus: np.ndarray, ids: np.ndarray,
               k: int) -> np.ndarray:
    """Plain-numpy exact L2 top-k (doc ids), independent of the code under
    test."""
    q = queries.astype(np.float64)
    x = corpus.astype(np.float64)
    d = (q * q).sum(1)[:, None] - 2.0 * q @ x.T + (x * x).sum(1)[None, :]
    part = np.argpartition(d, k, axis=1)[:, :k]
    order = np.take_along_axis(d, part, 1).argsort(1)
    return ids[np.take_along_axis(part, order, 1)]


def recall(result_ids: np.ndarray, truth: np.ndarray, k: int) -> float:
    hits = sum(len(set(r[:k].tolist()) & set(t[:k].tolist()))
               for r, t in zip(result_ids, truth))
    return hits / (len(truth) * k)


def build_service(cfg, *, partitions: int = 1, dispatch_mode: str = "serial",
                  max_vectors: int = FULL_PARTITION):
    """The deployment: ``cfg`` widths on the service's default partition
    size, 4 replicas, engine dispatch ``dispatch_mode``."""
    from repro.core import GraphConfig
    from repro.serve import EngineConfig, VectorCollectionService

    graph = GraphConfig(
        capacity=max_vectors + 1024, R=cfg.R, M=cfg.M, L_build=cfg.L_search,
        L_search=cfg.L_search, metric=cfg.metric, beam_width=cfg.beam_width,
    )
    check(graph.R_slack == cfg.R_slack, "graph slack != config R_slack")
    svc = VectorCollectionService(
        dim=cfg.dim, graph=graph, max_vectors_per_partition=max_vectors,
        initial_partitions=partitions, replicas=4,
        # provisioned throughput well above the smoke's burst: a 429 here
        # would be a budget artefact, not a device result
        engine_cfg=EngineConfig(beam_width=cfg.beam_width,
                                dispatch_mode=dispatch_mode,
                                tenant_ru_s=1e9),
    )
    return svc


def docs_for(ids: np.ndarray) -> list[dict]:
    return [{"id": int(i), "category": int(i) % CATEGORIES} for i in ids]


def load(svc, ids: np.ndarray, vecs: np.ndarray, partition_keys=None,
         chunk: int = 10_000, timer: CompileTimer | None = None) -> float:
    """Upsert through the service; returns wall seconds. With ``timer``,
    prints each chunk's wall and compile seconds."""
    t0 = time.perf_counter()
    for lo in range(0, len(ids), chunk):
        hi = min(lo + chunk, len(ids))
        pks = None if partition_keys is None else partition_keys[lo:hi]
        t1, c1 = time.perf_counter(), timer and timer.total_s
        svc.upsert(docs_for(ids[lo:hi]), vecs[lo:hi], partition_keys=pks)
        if timer:
            print(f"  loaded {hi}/{len(ids)} docs, "
                  f"{time.perf_counter() - t0:.1f}s; this chunk "
                  f"{time.perf_counter() - t1:.1f}s, compile "
                  f"{timer.total_s - c1:.1f}s", flush=True)
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve_batch(svc, queries: np.ndarray, k: int, L: int,
                predicate=None) -> np.ndarray:
    """Submit every query to the engine, drain, and return ids (B, k).
    Raises unless every response is a complete 200."""
    eng = svc.engine
    rids = [eng.submit_query(q, k=k, L=L, predicate=predicate)
            for q in queries]
    eng.drain()
    out = []
    for rid in rids:
        r = eng.pop_response(rid)
        check(r is not None and r.status == 200,
              f"request {rid}: status {getattr(r, 'status', None)}")
        check(r.complete and "+degraded" not in r.plan,
              f"request {rid}: incomplete answer, plan {r.plan}")
        out.append(r.ids)
    return np.stack(out)


def query_phase(svc, queries, corpus, ids, k: int, L: int) -> dict:
    t0 = time.perf_counter()
    got = serve_batch(svc, queries, k, L)
    wall = time.perf_counter() - t0
    truth = exact_topk(queries, corpus, ids, k)
    r = recall(got, truth, k)
    check(r >= RECALL_FLOOR, f"recall@{k} {r:.4f} < {RECALL_FLOOR}")
    return dict(recall=r, wall_s=wall, batches=svc.engine.metrics.batches)


def filtered_phase(svc, queries, k: int, L: int) -> dict:
    from repro.serve import F

    value = 3
    got = serve_batch(svc, queries, k, L, predicate=F.eq("category", value))
    returned = got[got >= 0]
    check(returned.size > 0, "filtered query returned nothing")
    bad = [int(d) for d in returned
           if svc.docs[int(d)]["category"] != value]
    check(not bad, f"filtered query returned non-matching docs {bad[:5]}")
    return dict(returned=int(returned.size))


def guarantee_phase(svc, new_ids, new_vecs, k: int, L: int) -> dict:
    """Acknowledged upserts are read back by their own vectors; a deleted
    doc never returns."""
    svc.upsert(docs_for(new_ids), new_vecs)
    got = serve_batch(svc, new_vecs, k, L)
    missing = [int(d) for d, row in zip(new_ids, got) if d not in row]
    check(not missing, f"upserted docs not read back: {missing}")
    gone = int(new_ids[0])
    svc.delete([gone])
    got = serve_batch(svc, new_vecs, k, L)
    check(not (got == gone).any(), f"deleted doc {gone} returned")
    check(all(d in row for d, row in zip(new_ids[1:], got[1:])),
          "surviving upserts lost after delete")
    return dict(upserted=len(new_ids), deleted=gone)


def kernel_phase(svc, queries, k: int, L: int, n_rows: int = 16_384) -> dict:
    """Each Pallas kernel, compiled for the device, against its ref.py on
    the loaded partition's own codebooks, codes and vectors."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import interpret_default
    from repro.kernels.flat_l2.kernel import flat_l2_pallas
    from repro.kernels.flat_l2.ref import flat_l2_ref
    from repro.kernels.pq_adc.kernel import pq_adc_pallas
    from repro.kernels.pq_adc.ref import pq_adc_ref
    from repro.kernels.pq_encode.kernel import pq_encode_pallas
    from repro.kernels.pq_encode.ref import pq_encode_ref
    from repro.kernels.topk_select.kernel import topk_select_pallas
    from repro.kernels.topk_select.ref import topk_select_ref

    interpret = interpret_default()
    part = svc.collection.partitions[0]
    idx, pv = part.index, part.providers
    n = min(n_rows, idx.count)
    schema = idx.schemas[-1]
    q = jnp.asarray(queries[:16])
    luts = idx._luts(queries[:16])[:, 0]  # (16, M, K): first live schema
    codes = jnp.asarray(pv.codes[:n])
    vecs = jnp.asarray(pv.vectors[:n])
    report = {}

    def run(name, fn, *args):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        t1 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        t2 = time.perf_counter()
        report[name] = dict(compile_s=t1 - t0, run_s=t2 - t1)
        return out

    with jax.default_matmul_precision("highest"):
        d_k = run("pq_adc", lambda a, b: pq_adc_pallas(
            a, b, interpret=interpret), luts, codes)
        d_r = pq_adc_ref(luts, codes)
        check(np.allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-5,
                          atol=1e-4), "pq_adc != ref")

        for LL in sorted({k, L}):
            v_k, i_k = run(f"topk_select[L={LL}]", lambda a, LL=LL:
                           topk_select_pallas(a, L=LL, interpret=interpret),
                           d_k)
            v_r, _ = topk_select_ref(d_k, L=LL)
            check(np.array_equal(np.asarray(v_k), np.asarray(v_r)),
                  f"topk_select L={LL} values != ref")
            dd = np.asarray(d_k)
            check(np.array_equal(
                np.take_along_axis(dd, np.asarray(i_k), 1), np.asarray(v_k)),
                f"topk_select L={LL} ids do not point at their values")

        f_k = run("flat_l2", lambda a, b: flat_l2_pallas(
            a, b, interpret=interpret), q, vecs)
        f_r = flat_l2_ref(q, vecs)
        check(np.allclose(np.asarray(f_k), np.asarray(f_r), rtol=1e-4,
                          atol=1e-2), "flat_l2 != ref")

        cb = schema.codebooks
        e_k = np.asarray(run("pq_encode", lambda a, b: pq_encode_pallas(
            a, b, interpret=interpret), vecs, cb))
        e_r = np.asarray(pq_encode_ref(vecs, cb))
    # a code may differ from the reference only where both centroids are
    # equally near (a float tie): compare their distances in float64
    diff = np.nonzero(e_k != e_r)
    if diff[0].size:
        M, K, dsub = cb.shape
        x = np.asarray(vecs, np.float64).reshape(n, M, dsub)[diff]
        c = np.asarray(cb, np.float64)
        dk = ((x - c[diff[1], e_k[diff]]) ** 2).sum(-1)
        dr = ((x - c[diff[1], e_r[diff]]) ** 2).sum(-1)
        check(np.allclose(dk, dr, rtol=1e-5, atol=1e-5),
              f"pq_encode picked farther centroids at {diff[0].size} codes")
    report["pq_encode"]["ties"] = int(diff[0].size)
    return report


def spmd_phase(cfg, seed: int, n_per_partition: int, n_chips: int,
               timer: CompileTimer | None = None) -> dict:
    """``n_chips`` partitions, one per chip, served by the spmd dispatch
    plane; the same collection under serial dispatch is the reference."""
    import jax

    from repro.partition.fanout import batched_fanout_search
    from repro.partition.partitioner import hash_key

    svc = build_service(cfg, partitions=n_chips, dispatch_mode="spmd",
                        max_vectors=n_per_partition)
    parts = svc.collection.partitions
    # partition keys chosen so each partition receives n_per_partition docs
    pks: list[list[int]] = [[] for _ in parts]
    key = 0
    while any(len(b) < n_per_partition for b in pks):
        h = hash_key(key)
        j = next(i for i, p in enumerate(parts) if p.owns(h))
        if len(pks[j]) < n_per_partition:
            pks[j].append(key)
        key += 1
    keys = np.asarray([pk for b in pks for pk in b])
    n = len(keys)
    corpus, _ = make_corpus(seed, n, cfg.dim)
    ids = np.arange(n)
    load_s = load(svc, ids, corpus, partition_keys=keys.tolist(),
                  timer=timer)
    check([p.num_docs for p in parts] == [n_per_partition] * n_chips,
          "partitions not evenly loaded")

    queries = make_queries(seed, N_QUERIES, cfg.dim)
    k, L = cfg.k, cfg.L_search
    t0 = time.perf_counter()
    spmd_ids = serve_batch(svc, queries, k, L)
    spmd_s = time.perf_counter() - t0

    fan = svc.engine._spmd()
    ((_stamp, arrs),) = fan._stacks.values()
    placement = {}
    for name, arr in arrs.items():
        if arr.ndim == 0 or arr.shape[0] != n_chips:
            continue
        rows = sorted((s.device.id, s.index[0].start) for s in
                      arr.addressable_shards)
        check(len({d for d, _ in rows}) == n_chips
              and sorted(r for _, r in rows) == list(range(n_chips))
              and all(s.data.shape[0] == 1 for s in arr.addressable_shards),
              f"{name}: not one partition per chip: {rows}")
        placement[name] = rows
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n_chips]}

    t0 = time.perf_counter()
    serial_ids = []
    for lo in range(0, len(queries), svc.engine.cfg.max_batch):
        got, _, info = batched_fanout_search(
            parts, queries[lo:lo + svc.engine.cfg.max_batch], k, L=L,
            batch_buckets=svc.engine.cfg.batch_buckets,
            beam_width=cfg.beam_width)
        check(info["complete"], "serial reference incomplete")
        serial_ids.append(got)
    serial_s = time.perf_counter() - t0
    serial_ids = np.concatenate(serial_ids)
    same = np.array_equal(spmd_ids, serial_ids)
    check(same, "spmd ids differ from serial dispatch")
    truth = exact_topk(queries, corpus, ids, k)
    return dict(docs=n, load_s=load_s, load_docs_per_s=n / load_s,
                spmd_s=spmd_s, serial_s=serial_s, ids_identical=same,
                recall=recall(spmd_ids, truth, k),
                placement=placement["neighbors"], peak_bytes=peaks)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); "
                 "nothing was run")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} but {len(devs)} TPU "
                 "device(s) visible")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import cosmosann
    from repro.launch.cache import enable_compile_cache

    devs = require_tpu(args.chips)
    enable_compile_cache()
    timer = CompileTimer()
    cfg = cosmosann.config()
    print(f"device: {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}; config {cfg.name}: dim={cfg.dim} M={cfg.M} "
          f"K={cfg.K} R={cfg.R} R_slack={cfg.R_slack} L={cfg.L_search} "
          f"k={cfg.k} W={cfg.beam_width}")
    print(REDUCED if args.chips == 1 else REDUCED_SPMD)

    if args.chips > 1:
        out = spmd_phase(cfg, args.seed, SPMD_PARTITION, args.chips, timer)
        print(f"spmd[{args.chips} chips]: {out['docs']} docs loaded in "
              f"{out['load_s']:.1f}s ({out['load_docs_per_s']:.1f} docs/s); "
              f"{N_QUERIES} queries spmd {out['spmd_s']:.2f}s, serial "
              f"{out['serial_s']:.2f}s; ids identical: "
              f"{out['ids_identical']}; recall@{cfg.k} {out['recall']:.4f}")
        print(f"placement (device id, partition): {out['placement']}")
        print(f"peak_bytes_in_use per device: {out['peak_bytes']}")
    else:
        n_bulk = FULL_PARTITION - N_NEW
        corpus, _ = make_corpus(args.seed, FULL_PARTITION, cfg.dim)
        ids = np.arange(FULL_PARTITION)
        svc = build_service(cfg)
        c0 = timer.total_s
        load_s = load(svc, ids[:n_bulk], corpus[:n_bulk], timer=timer)
        print(f"load: {n_bulk} docs in {load_s:.1f}s wall "
              f"({n_bulk / load_s:.1f} docs/s), of which compile "
              f"{timer.total_s - c0:.1f}s")
        queries = make_queries(args.seed, N_QUERIES, cfg.dim)
        k, L = cfg.k, cfg.L_search
        c0 = timer.total_s
        q = query_phase(svc, queries, corpus[:n_bulk], ids[:n_bulk], k, L)
        print(f"query: {N_QUERIES} queries in {q['batches']} micro-batches, "
              f"{q['wall_s']:.2f}s wall (compile {timer.total_s - c0:.1f}s); "
              f"recall@{k} {q['recall']:.4f} (floor {RECALL_FLOOR})")
        q2 = query_phase(svc, queries, corpus[:n_bulk], ids[:n_bulk], k, L)
        print(f"query (warm): {q2['wall_s']:.2f}s wall, "
              f"{N_QUERIES / q2['wall_s']:.1f} queries/s")
        f = filtered_phase(svc, queries[:16], k, L)
        print(f"filtered: {f['returned']} ids, all match category == 3")
        g = guarantee_phase(svc, ids[n_bulk:], corpus[n_bulk:], k, L)
        print(f"guarantees: {g['upserted']} upserts read back, doc "
              f"{g['deleted']} deleted and never returned; "
              f"{svc.collection.num_docs} docs live")
        for name, r in kernel_phase(svc, queries, k, L).items():
            print(f"kernel {name}: compiled {r['compile_s']:.2f}s, run "
                  f"{r['run_s'] * 1e3:.2f}ms, matches ref"
                  + (f" ({r['ties']} float ties)" if "ties" in r else ""))
    print(f"compile total: {timer.total_s:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
