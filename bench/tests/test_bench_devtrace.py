"""Trace reduction: busy union, kernel time and calls, idle gaps and their
attribution to program spans, on traces written by hand."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402

import cell  # noqa: E402
import devtrace  # noqa: E402

DEV = "/device:TPU:0"
KERNELS = cell.KERNELS
# op names as the TPU trace gives them: the HLO text of each op
KERNEL_OP = ('%_search_device.1 = (f32[8,256]{1,0:T(8,128)S(1)}, '
             's32[8,256]{1,0:T(8,128)S(1)}) custom-call(s32[1]{0:T(128)} '
             '%bitcast.4, f32[8,256]{1,0:T(8,128)} %queries.1), '
             'custom_call_target="tpu_custom_call"')


def _op(name, start_us, dur_us, plane=DEV):
    return {"plane": plane, "line": devtrace.OPS_LINE, "name": name,
            "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


def test_hand_made_trace():
    # profiler clock: the sync marker at 1,000 us is unix time 100.0 s
    events = [{"plane": "/host:CPU", "line": "t", "name": devtrace.SYNC,
               "start_ns": 1e6, "dur_ns": 10.0},
              _op("fusion.1", 2000, 100),
              _op(KERNEL_OP, 2050, 200),       # overlaps fusion.1
              _op("fusion.2", 3000, 500),
              _op(KERNEL_OP, 9000, 2000),      # crosses the end
              {"plane": DEV, "line": "XLA Modules", "name": "jit_x",
               "start_ns": 2e6, "dur_ns": 9e6}]       # not an op line
    spans = [{"name": "plan.dense", "start_unix": 100.0015, "dur_s": 0.0004},
             {"name": "scheduler.tick", "start_unix": 100.0005,
              "dur_s": 0.004}]
    t = devtrace.reduce(events, (100.0, 100.009), 100.0, KERNELS, spans)
    assert t.window_s == pytest.approx(9e-3)
    # busy: [2000, 2250] + [3000, 3500] + [9000, 10000] us
    assert t.busy_s == pytest.approx((250 + 500 + 1000) * 1e-6)
    assert t.kernel_calls["topk_mips"] == 2
    assert t.kernel_s["topk_mips"] == pytest.approx(2200e-6)
    # gaps [3500, 9000], [1000, 2000], [2250, 3000] us, each named by the
    # innermost span over its middle: none, the tick, the dense stage
    assert t.gaps == [("unattributed", pytest.approx(5500e-6)),
                      ("scheduler.tick", pytest.approx(1000e-6)),
                      ("plan.dense", pytest.approx(750e-6))]
    assert t.ops[0][0] == "%_search_device.1 = (f32[8,256], s32[8,256])"


def test_no_clock_marker_leaves_gaps_unattributed():
    events = [_op("a", 0, 10), _op("b", 30, 10)]
    t = devtrace.reduce(events, (0.0, 1.0), None, KERNELS,
                        [{"name": "x", "start_unix": 0.0, "dur_s": 1.0}])
    assert t.window_s == pytest.approx(40e-6)
    assert t.gaps == [("unattributed", pytest.approx(20e-6))]


def test_without_device_ops_nothing_is_read():
    assert devtrace.reduce([], (0.0, 1.0), None, KERNELS) is None


@pytest.mark.parametrize("ops,fails", [
    (["%fusion.1 = f32[8]{0} fusion()", KERNEL_OP], False),
    (["%fusion.1 = f32[8]{0} fusion()",
      KERNEL_OP.replace("_search_device", "_renamed_search")], True),
    (["%_search_device.3 = f32[8]{0} fusion()"], True)])
def test_a_named_kernel_missing_from_the_trace_fails_the_run(ops, fails):
    """A traced run whose window holds no op of a kernel its metrics name
    raises instead of dropping those metrics in silence."""
    import spec
    c = spec.resolve("rank-f32")
    events = [_op(n, 100 * i, 50) for i, n in enumerate(ops)]
    t = devtrace.reduce(events, (0.0, 1.0), None, cell.KERNELS)
    if fails:
        with pytest.raises(RuntimeError, match="topk_mips"):
            cell._require_kernels(c, t)
    else:
        cell._require_kernels(c, t)


def test_recorded_tpu_trace():
    """The reduction on a trace recorded on a TPU v5e: the kernel pattern
    finds the dense stage's Pallas call, one per search module."""
    import json
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "rank-f32.trace.json")) as f:
        d = json.load(f)
    ev = d["events"]
    t = devtrace.reduce(ev, (d["t0_unix"], d["end_unix"]), d["sync_unix"],
                        KERNELS)
    searches = [e for e in ev if e["line"] == "XLA Modules"
                and e["name"].startswith("jit__search_device(")]
    customs = [e for e in ev if e["line"] == devtrace.OPS_LINE
               and 'custom_call_target="tpu_custom_call"' in e["name"]]
    assert t.n_devices == 1
    assert t.kernel_calls["topk_mips"] == len(searches) == len(customs) > 0
    assert 0 < t.kernel_s["topk_mips"] <= t.busy_s <= t.window_s
    assert t.ops[0] == ("%_search_device.1 = (f32[8,64], s32[8,64])",
                        pytest.approx(t.kernel_s["topk_mips"]))
    assert all(name == "unattributed" for name, _ in t.gaps)  # no spans
