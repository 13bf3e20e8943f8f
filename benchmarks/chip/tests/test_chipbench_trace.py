"""The reduction from a profiler trace to busy, per-program time and idle
gaps, on synthetic events and on a small trace recorded on a v5e."""
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_testing  # noqa: F401  (sys.path)
from chipbench import spec
from chipbench.trace import (Event, load_events, module_key, summarize,
                             union_length)

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        ev(HOST, "python", "bench.traced", 0, 1000),
        ev(HOST, "python", "engine.pump", 100, 300),
        ev(HOST, "python", "harness.wait", 500, 400),
        ev(DEV, "XLA Modules", "jit__batched_search_entry(12)", 150, 100),
        ev(DEV, "XLA Modules", "jit_rerank(3)", 260, 20),
        ev(DEV, "XLA Ops", "fusion.1", 150, 60),
        ev(DEV, "XLA Ops", "fusion.2", 200, 50),  # overlaps fusion.1
        ev(DEV, "XLA Ops", "gather.3", 260, 20),
        ev(DEV, "XLA Ops", "copy.4", 1200, 50),  # after the window
    ]


def test_union_length():
    assert union_length([]) == 0
    assert union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert union_length([(3, 4), (0, 10)]) == 10


def test_busy_programs_and_idle_gaps_on_synthetic_events():
    t = summarize(synthetic())
    assert t.window_s == pytest.approx(1000e-9)
    # ops busy: [150, 250) and [260, 280)
    assert t.busy_s == pytest.approx(120e-9)
    assert t.program_s(["_batched_search_entry"]) == pytest.approx(100e-9)
    assert t.program_s(["_batched_search_entry", "rerank"]) == pytest.approx(120e-9)
    assert t.program_s(["batched_search"]) is None  # whole names only
    gaps = dict(t.idle_gaps())
    # idle: [0,150) [250,260) [280,1000) = 880 ns; the pump span covers
    # [100,150) [250,260) [280,400), the wait span [500,900)
    assert gaps["engine.pump"] == pytest.approx(180e-9)
    assert gaps["harness.wait"] == pytest.approx(400e-9)
    assert gaps["harness.loop"] == pytest.approx(300e-9)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)
    ops = t.device_ops()
    assert ops[0][0] == "jit__batched_search_entry"
    assert module_key("jit_rerank(3)") == "jit_rerank"


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        summarize([e for e in synthetic() if e.name != "bench.traced"])
    with pytest.raises(ValueError):
        summarize([e for e in synthetic() if e.plane == HOST])


def recorded():
    path = DATA / "trace_wiki768_v5e.json.gz"
    return summarize(load_events(path))


def test_recorded_v5e_trace():
    t = recorded()
    assert 0 < t.busy_s < t.window_s
    assert t.devices == [DEV]
    search = t.program_s(["_batched_search_entry"])
    rerank = t.program_s(["rerank"])
    assert search and rerank and search > rerank
    assert t.device_ops()[0][1] > 0
    names = {n for n, _ in t.idle_gaps()}
    assert names <= {"engine.pump", "engine.submit", "engine.pop",
                     "harness.wait", "harness.loop", "service.write_submit",
                     "device.in_program"}
    assert sum(s for _, s in t.idle_gaps(top=100)) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)


def test_metric_readers_on_the_recorded_trace():
    t = recorded()
    cell = spec.load_cell("wiki768.search")
    n = 200
    # the queries handed over in the traced span came in batches of 4;
    # those before it in full batches (a backlog the span does not see)
    batch = np.full(n, 16)
    batch[120:160] = 4
    window = SimpleNamespace(status=np.full(n, 200), batch=batch)
    traced = SimpleNamespace(batches=2, answered=20, sent=(120, 160),
                             counters={"hops_weighted": 900.0,
                                       "hops_lanes": 30})
    run = SimpleNamespace(cell=cell, window=window, trace=t, traced=traced,
                          peaks=spec.load_peaks("TPU v5 lite"))
    read = {m.name: spec.metric_reader(m.name)(run) for m in cell.per_layer}
    assert read["engine.batch_fill.search"] == 4.0
    assert read["search.rounds_per_query"] == 30.0
    assert read["search.device_ms_per_batch"] > 0
    assert 0 < read["rerank.roofline_share"] < 100
    assert 0 < read["device.idle_share.search"] < 100
    # nothing to read → nothing returned, never a 0 share
    run.trace = None
    assert spec.metric_reader("rerank.roofline_share")(run) is None
    run.traced = None
    assert spec.metric_reader("engine.batch_fill.search")(run) is None
    assert spec.metric_reader("search.rounds_per_query")(run) is None
    assert spec.metric_reader("write.device_ms_per_doc")(run) is None
