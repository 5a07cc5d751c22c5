"""Reduction of a profiler trace to device metrics.

`events_from_xplane` turns the profiler's `.xplane.pb` into plain event
dicts {plane, line, name, start_ns, dur_ns}; everything after works on that
list, so tests can run it on a trace written by hand.

Clocks: the benchmark marks the traced window with a host annotation
`bench.clock_sync` whose unix time it notes, which gives the offset
between the profiler's clock and the host's unix clock.  Program spans
(unix time) and the window bounds are moved onto the profiler's clock by
that offset; where no marker is found the gaps stay unattributed.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Sequence, Tuple

SYNC = "bench.clock_sync"
OPS_LINE = "XLA Ops"


def events_from_xplane(log_dir: str) -> List[dict]:
    """Device-plane events and the benchmark's host annotations of the
    newest trace under `log_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return []
    prof = ProfileData.from_file(paths[-1])
    out = []
    for plane in prof.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
    return out


def short_name(op: str) -> str:
    """An op's HLO text cut to its name and result type, layouts dropped:
    `%fusion.1 = f32[16,1,256]`."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:120]
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    result = rest[:rest.find(")") + 1] if rest.startswith("(") \
        else rest.split(" ", 1)[0]
    return f"{head} = {result}"[:120]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]          # pattern -> summed device time
    kernel_calls: Dict[str, int]
    ops: List[Tuple[str, float]]        # short op name -> seconds, top first
    gaps: List[Tuple[str, float]]       # attributed idle gaps, longest first
    n_devices: int


def reduce(events: Sequence[dict], window_unix: Tuple[float, float],
           sync_unix: Optional[float], kernels: Dict[str, str],
           spans: Sequence[dict] = (), top: int = 10) -> Optional[DeviceTrace]:
    """Busy time, per-kernel time and calls, the heaviest ops and the
    longest idle gaps inside the window, averaged over the device planes
    (a plane whose ops line is empty is not a device in use).

    `kernels` maps a metric's kernel name to a regex over op names;
    `spans` are program spans {name, start_unix, dur_s}."""
    sync = [e for e in events if e["name"] == SYNC]
    if sync and sync_unix is not None:
        offset = sync[0]["start_ns"] - sync_unix * 1e9
    else:
        offset = None
    ops_by_plane: Dict[str, List[dict]] = {}
    for e in events:
        if e["plane"].startswith("/device:") and e["line"] == OPS_LINE:
            ops_by_plane.setdefault(e["plane"], []).append(e)
    if not ops_by_plane:
        return None
    if offset is not None:
        w0 = window_unix[0] * 1e9 + offset
        w1 = window_unix[1] * 1e9 + offset
    else:       # no clock: the traced span of device activity is the window
        w0 = min(e["start_ns"] for v in ops_by_plane.values() for e in v)
        w1 = max(e["start_ns"] + e["dur_ns"]
                 for v in ops_by_plane.values() for e in v)
    pats = {k: re.compile(p) for k, p in kernels.items()}
    busy = 0.0
    k_s = {k: 0.0 for k in kernels}
    k_n = {k: 0 for k in kernels}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for evs in ops_by_plane.values():
        inside = []
        for e in evs:
            a = max(e["start_ns"], w0)
            b = min(e["start_ns"] + e["dur_ns"], w1)
            if b <= a:
                continue
            inside.append((a, b))
            name = short_name(e["name"])
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
            for k, rx in pats.items():
                if rx.search(e["name"]):
                    k_s[k] += e["dur_ns"] * 1e-9
                    k_n[k] += 1
        merged = _union(inside)
        busy += sum(b - a for a, b in merged) * 1e-9
        cursor = w0
        for a, b in merged + [(w1, w1)]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
    n = len(ops_by_plane)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_attribute(g, spans, offset), (g[1] - g[0]) * 1e-9)
             for g in gaps[:top]]
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return DeviceTrace(window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                       kernel_s={k: v / n for k, v in k_s.items()},
                       kernel_calls={k: v // n for k, v in k_n.items()},
                       ops=[(k, v / n) for k, v in ops], gaps=named,
                       n_devices=n)


def _attribute(gap: Tuple[float, float], spans: Sequence[dict],
               offset: Optional[float]) -> str:
    """The innermost program span covering the gap's middle, else
    'unattributed'."""
    if offset is None:
        return "unattributed"
    mid = (gap[0] + gap[1]) / 2
    best = None
    for sp in spans:
        a = sp["start_unix"] * 1e9 + offset
        b = a + sp["dur_s"] * 1e9
        if a <= mid <= b and (best is None or sp["dur_s"] < best["dur_s"]):
            best = sp
    return best["name"] if best else "unattributed"
