"""The program's own spans and counters as the harness reads them: idle
time under the innermost span, span and counter differences over a run,
the per-layer metrics that read them, and a metric added with a file
alone."""
import dataclasses
import json
import shutil
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_testing
from chipbench_testing import tiny_cell
from chipbench import cell as run_cell
from chipbench import spec
from chipbench.trace import Event, find_xplane, read_xplane, summarize
from chipbench.window import Profile, drive

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def host(name, start, dur, **stats):
    return Event(HOST, "python", name, float(start), float(dur),
                 stats or None)


def module(name, start, dur):
    return Event(DEV, "XLA Modules", name, float(start), float(dur))


def nested():
    """One micro-batch: engine.pump ⊃ serve.dispatch ⊃ search.partition ⊃
    (search.prepare, then search.graph, inside which the search program
    runs), then a harness wait. Idle outside the program: [0, 250) and
    [400, 1000)."""
    return [
        host("bench.traced", 0, 1000),
        host("engine.pump", 100, 500),
        host("serve.dispatch", 110, 480, batch=4, bucket=4),
        host("search.partition", 120, 460, pid=0),
        host("search.prepare", 130, 70),
        host("search.graph", 200, 300, W=4, L=100),
        host("harness.wait", 700, 200),
        module("jit__batched_search_entry(7)", 250, 150),
    ]


def test_idle_goes_to_the_innermost_span():
    t = summarize(nested())
    gaps = dict(t.idle_gaps(top=100))
    assert gaps == pytest.approx({
        "search.graph": 150e-9, "search.partition": 90e-9,
        "search.prepare": 70e-9, "serve.dispatch": 20e-9,
        "engine.pump": 20e-9, "harness.wait": 200e-9,
        "harness.loop": 300e-9})
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)
    assert t.idle_gaps(top=1) == [["harness.loop", pytest.approx(300e-9)]]


def test_span_s_sums_by_prefix_and_within_spans():
    t = summarize(nested())
    assert t.span_s(("search.",)) == pytest.approx(310e-9)
    assert t.span_s(("engine.", "serve.")) == pytest.approx(40e-9)
    batches = t.spans("serve.dispatch")
    assert [e.stats for e in batches] == [{"batch": 4, "bucket": 4}]
    assert t.span_s(("search.",), within=batches) == pytest.approx(310e-9)
    assert t.span_s(("search.",), within=t.spans("search.prepare")) == \
        pytest.approx(70e-9)
    assert t.span_s(("search.",), within=[]) == 0
    assert t.spans("write.delete") == []


def test_overlapping_spans_of_two_threads_split_the_gap():
    """Spans that overlap without nesting (two threads): each part goes to
    the one that opened last, and nothing is counted twice."""
    t = summarize([host("bench.traced", 0, 100), host("a.one", 0, 60),
                   host("b.two", 40, 60), module("jit_f(1)", 0, 10)])
    gaps = dict(t.idle_gaps())
    assert gaps == pytest.approx({"a.one": 30e-9, "b.two": 60e-9})


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_read_keeps_spans_of_the_form_and_no_jax_event(tmp_path):
    """The program's span and the harness's are kept, with their metadata;
    the host events JAX writes itself in the same trace are not."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro.core.spans import span

    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones(4096)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("engine.pump"):
            with span("search.graph", W=4, L=100):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = find_xplane(tmp_path)
    written = {e.name for p in ProfileData.from_file(str(path)).planes
               for line in p.lines for e in line.events}
    kept = {e.name: e for e in read_xplane(path)}
    assert set(kept) == {"engine.pump", "search.graph"}
    assert written - set(kept), "JAX wrote no host event of its own"
    assert kept["search.graph"].stats == {"W": 4, "L": 100}


WRITE_PROGRAM = {"upsert": "jit_insert_candidates(3)",
                 "delete": "jit_inplace_delete(5)"}


def writes(cycles=4):
    """Write requests one after another: an upsert of 8 docs (80 ns of
    device, 144 ns idle under write.insert), then 8 deletes of 1 doc (10 ns
    of device, 18 ns idle under write.delete ⊃ write.repair), so 10 ns of
    device and 18 ns of host per doc whichever requests a window holds
    whole. A search program runs between them."""
    ev, t = [], 0.0
    for _ in range(cycles):
        ev += [host("serve.ingest", t, 226, kind="upsert", docs=8),
               host("write.insert", t + 1, 224),
               module(WRITE_PROGRAM["upsert"], t + 50, 80)]
        t += 236
        for _ in range(8):
            ev += [host("serve.ingest", t, 30, kind="delete", docs=1),
                   host("write.delete", t + 1, 28),
                   host("write.repair", t + 5, 20),
                   module(WRITE_PROGRAM["delete"], t + 10, 10),
                   module("jit__batched_search_entry(7)", t + 31, 8)]
            t += 40
    return ev


@pytest.mark.parametrize("lo,hi", [(0, 2224), (130, 1800), (250, 2100),
                                   (17, 1013)])
def test_write_metrics_do_not_move_with_where_the_span_cuts(lo, hi):
    """The same writes under traced spans cut at other offsets, through
    the middle of an upsert or a delete: the repointed
    ``write.device_ms_per_doc`` and ``write.host_ms_per_doc`` read the
    same, since a write cut by an edge counts on neither side."""
    t = summarize([host("bench.traced", lo, hi - lo)] + writes())
    run = SimpleNamespace(trace=t)
    read = spec.metric_reader
    assert read("write.device_ms_per_doc")(run) == pytest.approx(10e-6)
    assert read("write.host_ms_per_doc")(run) == pytest.approx(18e-6)


def view(trace=None, counters=None):
    return SimpleNamespace(trace=trace, traced=None,
                           window=SimpleNamespace(counters=counters or {}))


def test_new_readers_and_when_they_have_nothing_to_read():
    read = spec.metric_reader
    batch = read("search.host_ms_per_batch")
    assert batch(view(summarize(nested()))) == pytest.approx(310e-6)
    assert batch(view()) is None  # nothing traced
    no_batch = [e for e in nested() if e.name != "serve.dispatch"]
    assert batch(view(summarize(no_batch))) is None
    hold = read("engine.hold_ms.stream")
    c = {"answers": 100, "answer_hold_ns": 4_100_000_000, "ingest_ops": 50,
         "upload_bytes": 1_492_500_000}
    assert hold(view(counters=c)) == pytest.approx(41.0)
    assert hold(view(counters={**c, "ingest_ops": 0})) is None  # no writes
    assert hold(view(counters={**c, "answers": 0})) is None
    assert hold(view()) is None
    upload = read("write.upload_mb_per_doc")
    assert upload(view(counters=c)) == pytest.approx(29.85)
    assert upload(view(counters={**c, "ingest_ops": 0})) is None
    for name in ("write.host_ms_per_doc", "write.device_ms_per_doc"):
        assert read(name)(view()) is None
        assert read(name)(view(summarize(nested()))) is None  # no writes


class HandProfile(Profile):
    """A traced span without the profiler: it takes the span totals and
    some counters by hand where the trace would start and stop."""

    def __init__(self, eng, *a):
        super().__init__(*a)
        self.eng = eng
        self.hand = []

    def _take(self):
        from repro.core.spans import totals

        m = self.eng.metrics
        parts = self.eng.collection.partitions
        self.hand.append((totals(), {
            "answers": m.answers, "ingest_ops": m.ingest_ops,
            "answer_hold_ns": m.answer_hold_ns,
            "upload_bytes": sum(p.providers.upload_bytes for p in parts),
            "repair_rows": sum(p.index.repair_rows for p in parts)}))

    def start_trace(self):
        self._take()

    def stop_trace(self):
        self._take()


@pytest.fixture(scope="module")
def stream_run():
    cell = tiny_cell("msturing100.stream")
    seed = 2**31 + 23
    setup = run_cell.set_up(cell, seed)
    queries, offsets = run_cell.window_queries(cell, setup, seed, 1.5)
    prof = HandProfile(setup.svc.engine, 0.5, 1.0, tempfile.mkdtemp())
    w = drive(setup.svc, queries, offsets, 1.5, setup.k, setup.L,
              n_live=setup.corpus.n_load, stream=setup.stream, profile=prof)
    return cell, w, prof


def test_counter_and_span_differences_over_a_run(stream_run):
    cell, w, prof = stream_run
    (s0, c0), (s1, c1) = prof.hand
    assert prof.spans == {n: (c - s0.get(n, (0, 0, 0))[0],
                              t - s0.get(n, (0, 0, 0))[1])
                          for n, (c, t, _) in s1.items()}
    assert {k: prof.counters[k] for k in c1} == {k: c1[k] - c0[k]
                                                 for k in c1}
    for d in (w, prof):
        assert all(v >= 0 for v in d.counters.values())
        assert all(c >= 0 and t >= 0 for c, t in d.spans.values())
    assert w.spans["serve.dispatch"][0] == w.batches > 0
    assert w.counters["answers"] == int(np.sum(w.status != 0))
    assert w.counters["ingest_ops"] >= w.write_ops > 0
    assert w.spans["serve.ingest"][0] > 0
    assert prof.spans["write.delete"][0] > 0  # the traced span holds one
    assert len(w.state_s) == 3  # the window's ends and the span's start


def test_a_metric_is_a_file_and_an_entry(stream_run, tmp_path, monkeypatch):
    """A per-layer metric that reads a span and a counter the program
    already has: a reader in ``metrics/`` and an entry in
    ``BENCHMARK.json``, no change to the harness."""
    cell, w, prof = stream_run
    bench = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(spec.BENCH_DIR / d, bench / d)
    entry = json.loads((spec.REPO_ROOT / "BENCHMARK.json").read_text())
    for name, unit in (("scratch.prepare_ms", "ms"),
                       ("scratch.repair_rows_per_delete", "rows")):
        entry["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "fan-out and search",
            "moves": "query_p50_ms", "workloads": ["msturing100.stream"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(entry))
    (bench / "metrics" / "scratch.prepare_ms.py").write_text(
        "def read(run):\n"
        "    n, ns = run.window.spans['search.prepare']\n"
        "    return ns * 1e-6 / n\n")
    (bench / "metrics" / "scratch.repair_rows_per_delete.py").write_text(
        "def read(run):\n"
        "    return (run.traced.counters['repair_rows']\n"
        "            / run.traced.spans['write.delete'][0])\n")
    monkeypatch.setattr(spec, "REPO_ROOT", tmp_path)
    monkeypatch.setattr(spec, "BENCH_DIR", bench)
    loaded = spec.load_cell("msturing100.stream")
    loaded = dataclasses.replace(loaded, config=cell.config,
                                 traffic=cell.traffic)
    got = run_cell.layer_metrics(loaded, run_cell.RunView(
        loaded, w, None, prof, {}))
    n, ns = w.spans["search.prepare"]
    assert got["scratch.prepare_ms"] == {"value": pytest.approx(ns * 1e-6 / n),
                                         "unit": "ms"}
    rows = prof.counters["repair_rows"] / prof.spans["write.delete"][0]
    assert got["scratch.repair_rows_per_delete"]["value"] == rows > 0
    # the program counters' metrics read too; the trace's stay silent
    assert got["engine.hold_ms.stream"]["value"] > 0
    assert got["write.upload_mb_per_doc"]["value"] > 0
    assert "write.host_ms_per_doc" not in got


def recorded(name):
    """A traced span recorded on a v5e with the program's spans (host spans
    and programs; the operations left out, so busy is the programs')."""
    from chipbench.trace import load_events

    return summarize(load_events(chipbench_testing.BENCH / "tests" / "data"
                                 / name))


def test_recorded_v5e_idle_goes_to_the_program_spans():
    from chipbench.trace import SPAN_RE

    t = recorded("trace_wiki768_spans_v5e.json.gz")
    gaps = t.idle_gaps(top=100)
    assert all(SPAN_RE.match(e.name) for e in t.host)
    assert {n for n, _ in gaps[:4]} == {"search.prepare", "search.graph",
                                        "search.rerank", "search.finish"}
    assert sum(s for _, s in gaps) == pytest.approx(t.window_s - t.busy_s,
                                                    rel=1e-9)
    assert len(t.spans("serve.dispatch")) == 60
    run = SimpleNamespace(trace=t)
    assert spec.metric_reader("search.host_ms_per_batch")(run) == \
        pytest.approx(11.3629834, rel=1e-6)
    assert spec.metric_reader("write.host_ms_per_doc")(run) is None


def test_recorded_v5e_stream_write_metrics():
    t = recorded("trace_stream_spans_v5e.json.gz")
    writes = t.spans("serve.ingest")
    assert sum(e.stats["docs"] for e in writes) == 24
    assert {e.stats["kind"] for e in writes} == {"upsert", "delete"}
    assert t.idle_gaps()[0][0] == "write.repair"
    run = SimpleNamespace(trace=t)
    read = spec.metric_reader
    assert read("write.device_ms_per_doc")(run) == pytest.approx(
        15.64695829, rel=1e-6)
    assert read("write.host_ms_per_doc")(run) == pytest.approx(
        30.95301771, rel=1e-6)
    assert read("search.host_ms_per_batch")(run) == pytest.approx(
        37.86732759, rel=1e-6)
