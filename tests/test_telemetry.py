"""Telemetry registry (obs/telemetry.py): Prometheus-exact histogram and
counter exposition, lock-correct concurrent recording checked against a
numpy oracle, scrape-while-recording consistency, per-request span trees
propagated frontend -> scheduler -> plan stages, bounded ring buffers for
traces and structured events (FIFO eviction), slow-query events, the JSONL
event sink, the disabled-mode no-op guarantees the overhead bench's
baseline relies on, and the mirror of every span into a profiler trace
(plus the gc and compile hooks) read back from a CPU profile."""
import gc
import glob
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core import MemoryScheduler, MemoryService
from repro.core.embedder import HashEmbedder
from repro.core.api import RetrieveRequest
from repro.core.extraction import Message
from repro.obs.telemetry import (DEFAULT_BUCKETS, GC_PAUSE, JIT_COMPILES,
                                 Counter, Histogram, Telemetry,
                                 get_telemetry, new_request_id,
                                 set_telemetry, span_names, walk_spans)
from repro.serving.frontend import MemoryFrontend


@pytest.fixture()
def tel():
    """A fresh registry swapped in as the process-wide one (restored on
    exit so the remaining suite keeps its accumulated metrics)."""
    prev = get_telemetry()
    t = set_telemetry(Telemetry(slow_query_s=None))
    yield t
    set_telemetry(prev)
    t.close()


# -- histograms: exact Prometheus semantics -----------------------------------

def test_histogram_exposition_exact():
    h = Histogram("memori_test_seconds", "a test histogram",
                  buckets=(0.1, 1.0))
    h.observe(0.05)          # le=0.1
    h.observe(0.1)           # boundary: buckets are closed above (v <= le)
    h.observe(0.5, n=3)      # le=1.0, batched
    h.observe(7.0)           # +Inf only
    assert h.exposition() == [
        "# HELP memori_test_seconds a test histogram",
        "# TYPE memori_test_seconds histogram",
        'memori_test_seconds_bucket{le="0.1"} 2',
        'memori_test_seconds_bucket{le="1"} 5',
        'memori_test_seconds_bucket{le="+Inf"} 6',
        "memori_test_seconds_sum 8.65",
        "memori_test_seconds_count 6",
    ]
    assert h.count == 6


def test_counter_exposition_exact():
    c = Counter("memori_test_things", "things that happened")
    c.inc()
    c.inc(2.5)
    assert c.exposition() == [
        "# HELP memori_test_things_total things that happened",
        "# TYPE memori_test_things_total counter",
        "memori_test_things_total 3.5",
    ]


def test_histogram_concurrent_observations_match_numpy_oracle():
    rng = np.random.default_rng(7)
    per_thread = [rng.gamma(2.0, 0.01, size=2000) for _ in range(8)]
    h = Histogram("memori_oracle_seconds", buckets=DEFAULT_BUCKETS)
    threads = [threading.Thread(
        target=lambda vals=vals: [h.observe(v) for v in vals])
        for vals in per_thread]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    everything = np.concatenate(per_thread)
    counts, total = h.snapshot()
    # oracle: right-closed buckets, exactly Prometheus's v <= le
    edges = np.array((-np.inf,) + tuple(DEFAULT_BUCKETS) + (np.inf,))
    want, _ = np.histogram(everything, bins=np.nextafter(edges, np.inf))
    assert counts.tolist() == want.tolist()
    assert h.count == everything.size            # no observation lost
    assert total == pytest.approx(float(everything.sum()), rel=1e-9)


def test_scrape_while_recording_stays_consistent():
    h = Histogram("memori_live_seconds", buckets=(0.001, 0.01, 0.1))
    stop = threading.Event()

    def recorder():
        i = 0
        while not stop.is_set():
            h.observe(0.0005 * (1 + i % 300))
            i += 1
    t = threading.Thread(target=recorder)
    t.start()
    try:
        last_count, last_sum = 0, 0.0
        for _ in range(300):
            counts, total = h.snapshot()
            cum = counts.sum()
            # cumulative count and sum only move forward, and each
            # snapshot's (counts, sum) pair is internally consistent
            assert cum >= last_count
            assert total >= last_sum - 1e-12
            assert total <= 0.15 * cum + 1e-9    # max observable value
            last_count, last_sum = cum, total
    finally:
        stop.set()
        t.join()
    assert h.count > 0


def test_histogram_rejects_empty_buckets():
    with pytest.raises(ValueError, match="bucket"):
        Histogram("memori_bad", buckets=())


# -- span trees ---------------------------------------------------------------

def test_span_tree_nesting_and_attrs(tel):
    tr = tel.start_trace("rid-1", op="retrieve")
    with tel.activate([tr]):
        with tel.span("outer", tenant="acme"):
            with tel.span("inner", batch=4) as sp:
                sp.set(launches=1)
        tr.add_completed("queued", 0.25)
    tel.finish_trace(tr)
    d = tel.get_trace("rid-1")
    assert span_names(d) == ["retrieve", "outer", "inner", "queued"]
    spans = {s["name"]: s for s in walk_spans(d["root"])}
    assert spans["outer"]["attrs"] == {"tenant": "acme"}
    assert spans["inner"]["attrs"] == {"batch": 4, "launches": 1}
    assert spans["inner"]["start_s"] >= spans["outer"]["start_s"]
    assert spans["queued"]["duration_s"] == 0.25
    assert d["duration_s"] >= spans["outer"]["duration_s"]


def test_activate_replaces_and_restores(tel):
    a = tel.start_trace("a", op="x")
    b = tel.start_trace("b", op="y")
    with tel.activate([a, None, a]):                  # dedup + None filter
        assert tel.current_traces() == [a]
        with tel.activate([b]):                       # REPLACE, not union
            with tel.span("only-b"):
                pass
        with tel.span("only-a"):
            pass
    tel.finish_trace(a)
    tel.finish_trace(b)
    assert span_names(tel.get_trace("a")) == ["x", "only-a"]
    assert span_names(tel.get_trace("b")) == ["y", "only-b"]


def test_span_survives_exception_unwind(tel):
    tr = tel.start_trace("boom", op="r")
    with pytest.raises(RuntimeError):
        with tel.activate([tr]):
            with tel.span("doomed"):
                raise RuntimeError("kaboom")
    tel.finish_trace(tr)
    d = tel.get_trace("boom")
    spans = {s["name"]: s for s in walk_spans(d["root"])}
    assert spans["doomed"]["duration_s"] is not None  # closed on unwind


def test_full_stack_span_tree_scheduler_to_plan(tel):
    """The tentpole acceptance path without HTTP: a traced retrieve
    submitted through the scheduler carries queue wait, the shared tick,
    and every executed plan stage in ONE tree."""
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800)
    sched = MemoryScheduler(svc, tick_interval_s=0.002, max_batch=16)
    try:
        svc.record("acme/c0", "s0",
                   [Message("U", "I live in Madrid.", 1.0)])
        tr = tel.start_trace("full-1", op="retrieve")
        fut = sched.submit_many(
            [RetrieveRequest(namespace="acme/c0", query="Which city?")],
            traces=[tr])[0]
        assert fut.result(timeout=30).status == "ok"
        tel.finish_trace(tr)
        names = span_names(tel.get_trace("full-1"))
        for want in ("queued", "scheduler.tick", "plan.embed", "plan.dense",
                     "plan.sparse", "plan.fuse", "plan.budget"):
            assert want in names, f"{want} missing from {names}"
        # the tick span closed before the future resolved: every span in
        # the serialized tree has a duration
        for s in walk_spans(tel.get_trace("full-1")["root"]):
            assert s["duration_s"] is not None
        # the plan stages carry the batch size the launch amortized
        spans = {s["name"]: s for s in walk_spans(
            tel.get_trace("full-1")["root"])}
        assert spans["plan.dense"]["attrs"]["batch"] >= 1
        assert spans["scheduler.tick"]["attrs"]["batch_size"] >= 1
    finally:
        sched.close()


# -- ring buffers + events ----------------------------------------------------

def test_trace_ring_evicts_oldest_first():
    tel = Telemetry(trace_capacity=4, slow_query_s=None)
    for i in range(6):
        tel.finish_trace(tel.start_trace(f"r{i}", op="x"))
    recent = [t["request_id"] for t in tel.recent_traces(limit=10)]
    assert recent == ["r2", "r3", "r4", "r5"]        # FIFO eviction
    assert tel.get_trace("r0") is None and tel.get_trace("r1") is None
    assert tel.get_trace("r5")["request_id"] == "r5"


def test_event_ring_evicts_oldest_first_and_filters():
    tel = Telemetry(event_capacity=3, slow_query_s=None)
    for i in range(5):
        tel.event("tick" if i % 2 else "tock", i=i)
    got = tel.events()
    assert [e["i"] for e in got] == [2, 3, 4]
    assert [e["i"] for e in tel.events(kind="tick")] == [3]
    assert [e["i"] for e in tel.events(limit=1)] == [4]


def test_slow_query_event_and_counter():
    tel = Telemetry(slow_query_s=0.0)
    tr = tel.start_trace("slowpoke", op="retrieve")
    tel.finish_trace(tr)
    tel.finish_trace(tr)                             # idempotent: one event
    evs = tel.events(kind="slow_query")
    assert len(evs) == 1 and evs[0]["request_id"] == "slowpoke"
    assert tel.counter("memori_slow_queries").value == 1


def test_jsonl_event_sink(tmp_path):
    path = str(tmp_path / "events.jsonl")
    tel = Telemetry(event_sink=path, slow_query_s=None)
    tel.event("admission_reject", tenants=["acme"], requests=3)
    tel.event("shard_down", shard=1)
    tel.close()
    rows = [json.loads(ln) for ln in
            open(path, encoding="utf-8").read().splitlines()]
    assert [r["kind"] for r in rows] == ["admission_reject", "shard_down"]
    assert rows[0]["tenants"] == ["acme"] and rows[1]["shard"] == 1
    assert all(r["ts"] > 0 for r in rows)


# -- disabled mode + ids ------------------------------------------------------

def test_disabled_telemetry_is_a_no_op():
    tel = Telemetry(enabled=False)
    assert tel.start_trace("x", op="y") is None
    tel.inc("memori_nope")
    tel.observe("memori_nada", 0.5)
    with tel.activate([None]):
        with tel.span("ghost") as sp:
            sp.set(batch=1)                          # handle still works
    tel.finish_trace(None)
    tel.event("invisible")
    assert tel.metrics() == [] and tel.events() == []
    assert tel.recent_traces() == [] and tel.render() == ""


def test_request_ids_are_unique_hex():
    ids = {new_request_id() for _ in range(256)}
    assert len(ids) == 256
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)


def test_registry_reuses_metric_instances():
    tel = Telemetry()
    h1 = tel.histogram("memori_same_seconds")
    h2 = tel.histogram("memori_same_seconds")
    assert h1 is h2
    c1 = tel.counter("memori_same_things")
    assert tel.counter("memori_same_things") is c1


# -- the profiler mirror --------------------------------------------------------

def _host_lines(log_dir, fn):
    """Run `fn` under a profiler session; the event names of each host
    thread line, in the plane's line order."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    prof = ProfileData.from_file(path)
    return [[e.name for e in line.events] for plane in prof.planes
            if plane.name.startswith("/host:") for line in plane.lines]


def _post(fe, path, body):
    req = urllib.request.Request(
        fe.address + path, data=json.dumps(body).encode(),
        headers={"Authorization": "Bearer key-acme"}, method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())


def test_served_tick_spans_reach_the_profile(tel, tmp_path):
    """Retrieves over HTTP: the tick, the wait between two ticks, the plan
    stages, the device wait and the hand-back on one host line (the
    scheduler's), the response written on another (the handler's)."""
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800)
    sched = MemoryScheduler(svc, tick_interval_s=0.002, max_batch=16)
    fe = MemoryFrontend(svc, {"key-acme": "acme"}).start()
    query = {"namespace": "c0", "query": "Which city?"}
    try:
        _post(fe, "/v1/record", {"namespace": "c0", "session_id": "s0",
                                 "messages": [{"speaker": "U",
                                               "text": "I live in Madrid.",
                                               "timestamp": 1.0}]})
        _post(fe, "/v1/retrieve", query)            # compiles outside
        # two ticks: an annotation records only if it opens and closes
        # inside the session, and the first wait opened before it
        lines = _host_lines(tmp_path, lambda: [
            _post(fe, "/v1/retrieve", query) for _ in range(2)])
    finally:
        fe.close()
        sched.close()
    [tick_line] = [ln for ln in lines if "scheduler.tick" in ln]
    for want in ("scheduler.wait", "plan.embed", "plan.dense", "plan.fuse",
                 "plan.budget", "device.wait", "scheduler.resolve"):
        assert want in tick_line, f"{want} missing from {set(tick_line)}"
    handler = [ln for ln in lines if "frontend.respond" in ln]
    assert handler and all("frontend" in ln for ln in handler)
    assert tick_line not in handler


@pytest.mark.parametrize("enabled", [True, False])
def test_span_annotates_without_an_active_trace(tmp_path, enabled):
    """A span with no request trace still lands in the profile; a
    disabled registry emits nothing there, as everywhere else."""
    t = Telemetry(enabled=enabled, slow_query_s=None)

    def work():
        with t.span("lonely.span", batch=3):
            pass
    names = {n for ln in _host_lines(tmp_path, work) for n in ln}
    assert ("lonely.span" in names) == enabled
    assert t.recent_traces() == []


def test_gc_pause_is_annotated_and_observed(tel, tmp_path):
    with tel.span("first.span"):   # loads the annotation class; the gc
        pass                       # hook itself never imports
    hist = tel.histogram(GC_PAUSE)
    was = gc.isenabled()
    gc.disable()              # only the collection below runs
    try:
        before = hist.count
        names = {n for ln in _host_lines(tmp_path, gc.collect) for n in ln}
        assert hist.count == before + 1
    finally:
        if was:
            gc.enable()
    assert "gc.pause" in names
    assert f"# TYPE {GC_PAUSE} histogram" in tel.render()


def test_gc_pause_never_waits_on_the_histogram_lock(tel):
    """A collection that starts while its own thread holds the pause
    histogram's lock (a scrape allocating inside `snapshot()`) returns,
    and its pause is counted at the next read."""
    hist = tel.histogram(GC_PAUSE)
    was = gc.isenabled()
    gc.disable()              # only the collection below runs
    try:
        before = hist.count
        done = threading.Event()

        def collect_under_lock():
            with hist._lock:
                gc.collect()
            done.set()
        threading.Thread(target=collect_under_lock, daemon=True).start()
        assert done.wait(30), "the gc hook blocked on the histogram lock"
        assert hist.count == before + 1
    finally:
        if was:
            gc.enable()


def test_only_the_global_registry_exports_gc_pauses(tel):
    """The hook feeds the process-wide registry alone, so a registry that
    never became it exports no (forever empty) pause histogram."""
    assert f"# TYPE {GC_PAUSE} histogram" in tel.render()
    other = Telemetry(slow_query_s=None)
    assert GC_PAUSE not in other.render()
    other.close()


def test_jit_compiles_are_counted_and_land_in_active_trees(tel):
    import jax
    import jax.numpy as jnp
    with tel.span("first.span"):          # registers the compile listener
        pass
    scale = float(time.time_ns() % 1_000_003)     # a program never seen
    f = jax.jit(lambda x: x * scale + 1.0)
    tr = tel.start_trace("compiles", op="retrieve")
    with tel.activate([tr]):
        f(jnp.ones(5)).block_until_ready()
    compiled = tel.counter(JIT_COMPILES).value
    assert compiled >= 1
    f(jnp.ones(5)).block_until_ready()           # warm: no compile
    assert tel.counter(JIT_COMPILES).value == compiled
    tel.finish_trace(tr)
    spans = [s for s in walk_spans(tel.get_trace("compiles")["root"])
             if s["name"] == "jit.compile"]
    assert spans and all(s["duration_s"] > 0 for s in spans)
    assert all(s["start_s"] >= 0 for s in spans)    # back-dated inside


def test_request_tree_serialises_as_before(tel):
    """Per-request trees keep their form: the same keys at every level,
    JSON-clean, and only spans opened under the request's own trace
    (the scheduler's wait and hand-back, the response write and gc
    pauses stay out)."""
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800)
    sched = MemoryScheduler(svc, tick_interval_s=0.002, max_batch=16)
    try:
        svc.record("acme/c0", "s0",
                   [Message("U", "I live in Madrid.", 1.0)])
        tr = tel.start_trace("form-1", op="retrieve")
        fut = sched.submit_many(
            [RetrieveRequest(namespace="acme/c0", query="Which city?")],
            traces=[tr])[0]
        assert fut.result(timeout=30).status == "ok"
        gc.collect()
        tel.finish_trace(tr)
    finally:
        sched.close()
    d = tel.get_trace("form-1")
    assert json.loads(json.dumps(d)) == d
    assert set(d) == {"request_id", "op", "started_unix", "duration_s",
                      "root"}
    for sp in walk_spans(d["root"]):
        assert set(sp) <= {"name", "start_s", "duration_s", "attrs",
                           "children"}
        assert {"name", "start_s", "duration_s"} <= set(sp)
    names = span_names(d)
    assert names[:3] == ["retrieve", "queued", "scheduler.tick"]
    assert not {"scheduler.wait", "scheduler.resolve", "frontend.respond",
                "gc.pause"} & set(names)
    tick = next(s for s in walk_spans(d["root"])
                if s["name"] == "scheduler.tick")
    assert [c["name"] for c in tick["children"]] == [
        "plan.embed", "plan.dense", "plan.sparse", "plan.fuse",
        "device.wait", "plan.budget"]
