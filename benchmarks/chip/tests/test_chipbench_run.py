"""A whole run on the CPU at a small size, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, the
check comes out false."""
import dataclasses
import time

import numpy as np
import pytest

from chipbench_testing import tiny_cell
from chipbench import cell as run_cell
from chipbench.spec import BENCH_DIR


@pytest.fixture(scope="module")
def counter():
    return run_cell.CompileCounter()


def run(cell, counter, seconds=1.0):
    return run_cell.run(cell, seed=2**31 + 17, seconds=seconds, trace=False,
                        t_process=time.perf_counter(), counter=counter)


@pytest.mark.parametrize("workload", ["wiki768.search", "msturing100.stream"])
def test_sound_run_is_correct(workload, counter):
    res = run(tiny_cell(workload), counter)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check" and res["failed"] == 0
    assert res["attempted"] == 40
    m = res["metrics"]
    assert {"query_p50_ms", "query_p95_ms", "recall_at_10", "setup_s"} <= set(m)
    assert 0 < m["query_p50_ms"]["value"] <= m["query_p95_ms"]["value"]
    assert 0.5 < m["recall_at_10"]["value"] <= 1.0
    if workload.endswith("stream"):
        assert m["write_docs_per_s"]["value"] > 0
    assert res["device"]["count"] >= 1


@pytest.mark.parametrize("workload,traffic", [
    ("wiki768.search", {"filter": {"category": 3}}),
    ("wiki768.search", {"arrivals": {"kind": "on_off", "on_s": 0.25,
                                     "off_s": 0.25}}),
    ("msturing100.stream", {"writes": {"upsert_docs": 16,
                                       "stream_docs": 512}}),
])
def test_mixes_made_of_data_alone_run_correct(workload, traffic, counter):
    """A filtered mix, a bursty mix and an upsert-only ingest mix need a
    traffic file and no code."""
    cell = tiny_cell(workload)
    cell = dataclasses.replace(cell, traffic={**cell.traffic, **traffic})
    res = run(cell, counter)
    assert res["correct"], res["check"]
    assert res["attempted"] == 40 and res["failed"] == 0
    if "writes" in traffic:
        assert res["metrics"]["write_docs_per_s"]["value"] > 0


def test_a_filtered_mix_holds_the_program_to_its_filter(counter,
                                                         monkeypatch):
    from repro.serve.vector_engine import VectorServeEngine

    orig = VectorServeEngine.submit_query

    def unfiltered(self, vector, k=10, L=None, **kw):
        kw["predicate"] = None
        return orig(self, vector, k, L, **kw)

    monkeypatch.setattr(VectorServeEngine, "submit_query", unfiltered)
    cell = tiny_cell("wiki768.search")
    cell = dataclasses.replace(cell, traffic={**cell.traffic,
                                              "filter": {"category": 3}})
    res = run(cell, counter)
    assert not res["correct"]
    assert res["check"]["bad_answers"]["value"] > 0


def _alter_answer(monkeypatch, n_load):
    from repro.partition.partitioner import PhysicalPartition

    orig = PhysicalPartition.search_batch

    def altered(self, queries, k, L=None, **kw):
        ids, dists, ru, stats = orig(self, queries, k, L, **kw)
        ids = np.array(ids)
        ids[0, 0] = next(d for d in range(len(self.index.doc_to_slot))
                         if d in self.index.doc_to_slot and d not in ids[0])
        return ids, dists, ru, stats

    monkeypatch.setattr(PhysicalPartition, "search_batch", altered)


def _miss_nearest(monkeypatch, n_load):
    """A search that misses the nearest neighbours: the 6th to (k+5)th
    nearest it finds, with their exact distances, in order (recall 0.5, as
    the rerank cut to k reads on the chip)."""
    from repro.partition.partitioner import PhysicalPartition

    orig = PhysicalPartition.search_batch

    def missing(self, queries, k, L=None, **kw):
        ids, dists, ru, stats = orig(self, queries, k + 5, L, **kw)
        return (np.asarray(ids)[:, 5:], np.asarray(dists)[:, 5:], ru,
                stats)

    monkeypatch.setattr(PhysicalPartition, "search_batch", missing)


def _skip_deletes(monkeypatch, n_load):
    from repro.partition.partitioner import PhysicalPartition

    monkeypatch.setattr(PhysicalPartition, "delete", lambda self, ids: 0.0)


def _drop_new_upserts(monkeypatch, n_load):
    from repro.partition.partitioner import PhysicalPartition

    orig = PhysicalPartition.insert

    def dropped(self, doc_ids, pk_hashes, vectors, props=None):
        if min(int(d) for d in doc_ids) >= n_load:
            return 0.0, 0.0  # acknowledged, never applied
        return orig(self, doc_ids, pk_hashes, vectors, props)

    monkeypatch.setattr(PhysicalPartition, "insert", dropped)


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("wiki768.search", _alter_answer, ("dist_gap", "bad_answers")),
    ("msturing100.stream", _alter_answer, ("dist_gap", "bad_answers")),
    ("wiki768.search", _miss_nearest, ("recall_miss",)),
    ("msturing100.stream", _miss_nearest, ("recall_miss",)),
    ("msturing100.stream", _skip_deletes,
     ("bad_answers", "deleted_returned")),
    ("msturing100.stream", _drop_new_upserts, ("readback_missing",)),
])
def test_broken_timed_path_is_not_correct(workload, fault, caught_by,
                                          counter, monkeypatch):
    cell = tiny_cell(workload)
    fault(monkeypatch, cell.config["docs_loaded"])
    res = run(cell, counter)
    assert not res["correct"]
    assert any(res["check"][n]["value"] > res["check"][n]["limit"]
               for n in caught_by), res["check"]


@pytest.mark.parametrize("workload", ["wiki768.search", "msturing100.stream"])
def test_bf16_control_is_not_correct(workload, counter):
    """The control of ``control.py``: the reference with its vectors in
    bfloat16, put in the program's place for the window's queries."""
    import importlib.util

    path = BENCH_DIR / "control.py"
    s = importlib.util.spec_from_file_location("chipbench_control", path)
    control = importlib.util.module_from_spec(s)
    s.loader.exec_module(control)
    rows = []
    cell = tiny_cell(workload)
    L = cell.config["graph"]["L_search"]
    o = run_cell.execute(cell, 2**31 + 5, 1.0, False, time.perf_counter(),
                         counter, control.replays([L, 12], 32, rows, True))
    assert run_cell.compare(o).correct
    # the replays ask the window's queries again at the full and a cut
    # search list (at this size both find every neighbour), and with the
    # rerank cut to the k best by ADC, which misses some
    assert [r[0] for r in rows] == [f"replay_L{L}", "replay_L12",
                                    "replay_rerank_k"]
    assert all(0.0 <= miss <= 0.1 and bad == 0 for _, miss, bad in rows[:2])
    assert rows[2][1] > rows[0][1] and rows[2][2] == 0
    ctrl = control.control_check(o)
    assert not ctrl.correct
    gap, limit = ctrl.numbers["dist_gap"]
    assert gap > limit
