"""The program's spans against the device's idle time (bench/hosttrace.py)
and the readers of the span metrics, on traces written by hand."""
import json
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402

import cell  # noqa: E402
import devtrace  # noqa: E402
import hosttrace  # noqa: E402
import spec  # noqa: E402

HOST = "/host:CPU"
DEV = "/device:TPU:0"


def _ev(name, a_us, b_us, line_index, plane=HOST):
    # every host thread line carries the process's name, not the thread's
    return {"plane": plane, "line": "python3", "line_index": line_index,
            "name": name, "start_ns": a_us * 1e3,
            "dur_ns": (b_us - a_us) * 1e3}


def _op(a_us, b_us):
    return {"plane": DEV, "line": devtrace.OPS_LINE, "name": "%fusion.1",
            "start_ns": a_us * 1e3, "dur_ns": (b_us - a_us) * 1e3}


# scheduler line first, then a handler line; the window is [0, 1000] us
SCHED = [("scheduler.wait", 0, 100), ("scheduler.tick", 100, 490),
         ("plan.embed", 110, 200), ("plan.dense", 200, 350),
         ("dense.rescore", 260, 340), ("device.wait", 270, 300),
         ("plan.fuse", 350, 360), ("device.wait", 360, 450),
         ("plan.budget", 450, 490), ("scheduler.resolve", 490, 500),
         ("scheduler.wait", 500, 600),
         ("scheduler.tick", 600, 900), ("plan.embed", 600, 700),
         ("device.wait", 700, 880), ("scheduler.wait", 900, 1000)]
HANDLER = [("frontend", 50, 60), ("gc.pause", 300, 320),
           ("frontend.respond", 520, 530), ("frontend", 610, 612),
           ("gc.pause", 700, 705), ("frontend.respond", 905, 909)]
OPS = [(210, 260), (300, 450), (700, 880)]


def _trace(sched_line=0, handler_line=1):
    host = ([_ev(n, a, b, sched_line) for n, a, b in SCHED]
            + [_ev(n, a, b, handler_line) for n, a, b in HANDLER])
    return host, [_op(a, b) for a, b in OPS]


@pytest.mark.parametrize("lines", [(0, 1), (1, 0)])
def test_hand_made_host_trace(lines):
    """Every number of `summarize`, exact, whichever position the
    scheduler's line has among lines of one name."""
    host, ops = _trace(*lines)
    got = hosttrace.summarize(host, ops, 0.0, 1e6)
    # idle [0,210] [260,300] [450,700] [880,1000]; ticks [100,490] [600,900]
    assert got["scheduler_line"] == [HOST, lines[0]]
    assert got["ticks"] == 2
    assert got["device_idle_pct"] == pytest.approx(62.0)
    assert got["idle_in_tick_pct"] == pytest.approx(31.0)
    assert got["idle_between_ticks_pct"] == pytest.approx(31.0)
    # idle by the innermost scheduler-line span over it, per tick
    assert got["idle_ms_per_tick"] == pytest.approx({
        "scheduler.wait": 0.15, "plan.embed": 0.095, "plan.budget": 0.02,
        "scheduler.tick": 0.015, "device.wait": 0.015,
        "scheduler.resolve": 0.005, "plan.dense": 0.005,
        "dense.rescore": 0.005})
    assert got["tick_ms"] == pytest.approx(0.345)
    assert got["wait_ms_per_tick"] == pytest.approx(0.15)
    assert got["resolve_ms_per_tick"] == pytest.approx(0.005)
    assert got["tick_wait_cover_pct"] == pytest.approx(99.0)
    assert got["tick_children_pct"] == pytest.approx(100.0 * 660 / 690)
    assert got["fuse_ms"] == pytest.approx(0.01)
    assert got["rescore_ms"] == pytest.approx(0.05)
    assert got["frontend_host_ms"] == pytest.approx(0.013)
    assert got["respond_ms"] == pytest.approx(0.007)
    assert got["gc_pause_max_ms"] == pytest.approx(0.02)
    assert got["gc_pauses"] == 2


def test_window_clips_and_excludes():
    """Idle and cover shares count only the window; events that start
    outside it are not read."""
    host, ops = _trace()
    got = hosttrace.summarize(host, ops, 150e3, 950e3)
    assert got["ticks"] == 1                          # [600, 900] only
    # idle [150,210] [260,300] [450,700] [880,950]; in ticks: 60+40+40+100+20
    assert got["device_idle_pct"] == pytest.approx(100 * 420 / 800)
    assert got["idle_in_tick_pct"] == pytest.approx(100 * 260 / 800)
    assert got["tick_wait_cover_pct"] == pytest.approx(100 * 790 / 800)
    assert sum(got["idle_ms_per_tick"].values()) == pytest.approx(0.42)
    assert got["gc_pause_max_ms"] == pytest.approx(0.02)


def test_no_scheduler_tick_line_raises_naming_the_lines():
    host = [_ev(n, a, b, 1) for n, a, b in HANDLER]
    with pytest.raises(RuntimeError, match=r"scheduler\.tick.*python3"):
        hosttrace.summarize(host, [_op(0, 10)], 0.0, 1e6)


def test_idle_intervals_match_the_harness_busy_time():
    """On the trace recorded on a TPU v5e, the idle intervals are the
    complement of the busy time `devtrace.reduce` reports."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "rank-f32.trace.json")) as f:
        d = json.load(f)
    ev = d["events"]
    t = devtrace.reduce(ev, (d["t0_unix"], d["end_unix"]), d["sync_unix"],
                        cell.KERNELS)
    sync = next(e for e in ev if e["name"] == devtrace.SYNC)
    off = sync["start_ns"] - d["sync_unix"] * 1e9
    w0, w1 = d["t0_unix"] * 1e9 + off, d["end_unix"] * 1e9 + off
    idle = hosttrace.device_idle(ev, w0, w1)
    assert len(idle) == t.n_devices == 1
    idle_s = sum(b - a for gaps in idle.values() for a, b in gaps) * 1e-9
    assert idle_s == pytest.approx(t.window_s - t.busy_s, rel=1e-9)


# -- the span readers of BENCHMARK.json ----------------------------------------

def _span(name, start, dur):
    return {"name": name, "start_unix": 100.0 + start, "dur_s": dur,
            "requests": {"r0"}}


SPANS = [_span("scheduler.tick", 0.0, 0.4), _span("scheduler.tick", 0.6, 0.3),
         _span("plan.dense", 0.2, 0.15), _span("dense.rescore", 0.26, 0.08),
         _span("device.wait", 0.27, 0.03), _span("plan.fuse", 0.35, 0.01),
         _span("device.wait", 0.36, 0.09), _span("plan.fuse", 0.61, 0.02),
         _span("device.wait", 0.7, 0.18)]
# a program without device.wait or dense.rescore spans (the fuse span
# still holds the wait there)
OLD = [s for s in SPANS if s["name"] not in ("device.wait", "dense.rescore")]


@pytest.mark.parametrize("metric,spans,want", [
    ("device.wait_ms", SPANS, 150.0),
    ("plan.fuse_ms", SPANS, 15.0),
    ("dense.rescore_ms", SPANS, 50.0),
    ("device.wait_ms", OLD, None),
    ("plan.fuse_ms", OLD, 15.0),
    ("dense.rescore_ms", OLD, None),
    ("device.wait_ms", [], None)])
def test_span_readers(metric, spans, want):
    got = spec.load_reader(metric)(SimpleNamespace(spans=spans))
    assert got == (None if want is None else pytest.approx(want))


def test_span_readers_are_declared():
    bench = spec.load_benchmark()
    names = {m["name"]: m for m in bench["per_layer"]}
    for metric in ("device.wait_ms", "plan.fuse_ms", "dense.rescore_ms"):
        assert names[metric]["source"] == "program_span"
    assert names["dense.rescore_ms"]["workloads"] == ["rank-int8"]
    assert [m["name"] for m in spec.resolve("rank-f32").per_layer
            if m["name"] == "dense.rescore_ms"] == []
