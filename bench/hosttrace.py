"""The program's spans in a profiler trace, read against the device's idle
time.  Not part of a benchmark run: `bench/devtrace.py` keeps only device
ops and the clock marker, so the harness cannot read host lines yet.

    python3 bench/hosttrace.py --workload <cell> --seed <n> --seconds 10

runs one traced run of the cell exactly as `bench/run.py --trace 1` does,
keeps the host events whose names are program spans (`SPANS`), and prints
the run's result line with a `host` object added (`summarize`):

* `idle_in_tick_pct` / `idle_between_ticks_pct` — the window's device idle
  time inside / outside the union of `scheduler.tick` events, in % of the
  window, averaged over device planes;
* `idle_ms_per_tick` — all the window's idle time split by the innermost
  span of the scheduler line over it (`unattributed` where none is), per
  tick;
* `wait_ms_per_tick` (summed `device.wait` on the scheduler line),
  `resolve_ms_per_tick` (summed `scheduler.resolve`), `fuse_ms`,
  `rescore_ms` (each `dense.rescore` less its nested `device.wait`),
  `frontend_host_ms` (`frontend` + `frontend.respond` per request),
  `respond_ms` (per `frontend.respond`) and `gc_pause_max_ms`;
* `tick_wait_cover_pct` — the share of the window that `scheduler.tick`
  and `scheduler.wait` cover on the scheduler line, and
  `tick_children_pct` — the spans directly inside the ticks over the
  ticks' summed time.

Host thread lines are all named after the process, so lines are told apart
by their position in the plane (`line_index`); the scheduler's line is the
one that holds `scheduler.tick`.  Program events are read on the
profiler's own clock; only the window's bounds come through the clock
marker, as in `devtrace.reduce`.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Sequence, Tuple  # noqa: E402

import devtrace  # noqa: E402

SPANS = ("scheduler.wait", "scheduler.tick", "scheduler.resolve",
         "plan.embed", "plan.dense", "plan.sparse", "plan.graph",
         "plan.fuse", "plan.budget", "device.wait", "dense.rescore",
         "frontend", "frontend.respond", "admission", "gc.pause")
TICK = "scheduler.tick"

Interval = Tuple[float, float]


def host_events(log_dir: str) -> List[dict]:
    """Program-span events on the host planes of the newest trace under
    `log_dir`, each with its line's position in the plane."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return []
    prof = ProfileData.from_file(paths[-1])
    out = []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in SPANS:
                    out.append({"plane": plane.name, "line": line.name,
                                "line_index": i, "name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
    return out


def _clip(ivs: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    return [(max(a, w0), min(b, w1)) for a, b in ivs
            if min(b, w1) > max(a, w0)]


def _overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Summed overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_idle(events: Sequence[dict], w0: float,
                w1: float) -> Dict[str, List[Interval]]:
    """Per device plane: every idle interval of the window [w0, w1] (ns),
    the complement of the union of its ops, as `devtrace.reduce` counts
    busy time."""
    by_plane: Dict[str, List[Interval]] = {}
    for e in events:
        if (e["plane"].startswith("/device:")
                and e["line"] == devtrace.OPS_LINE):
            by_plane.setdefault(e["plane"], []).append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    idle = {}
    for plane, ops in by_plane.items():
        gaps, cursor = [], w0
        for a, b in devtrace._union(_clip(ops, w0, w1)) + [(w1, w1)]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        idle[plane] = gaps
    return idle


def scheduler_line(host: Sequence[dict]) -> Tuple[str, int]:
    """(plane, line_index) of the line that holds `scheduler.tick`; raises,
    naming the lines seen, when none does, so a renamed span cannot make
    the in-tick metrics go silent."""
    for e in host:
        if e["name"] == TICK:
            return e["plane"], e["line_index"]
    seen = sorted({(e["plane"], e["line_index"], e["line"]) for e in host})
    raise RuntimeError(f"no host line holds a {TICK!r} event; program "
                       f"events were seen on lines {seen}")


def _on_line(host: Sequence[dict], key: Tuple[str, int]) -> List[dict]:
    return sorted((e for e in host if (e["plane"], e["line_index"]) == key),
                  key=lambda e: (e["start_ns"], -e["dur_ns"]))


def _tree(events: Sequence[dict]) -> List[dict]:
    """Nest one line's events by containment (sorted by start, longest
    first): each gets its `children`."""
    roots: List[dict] = []
    stack: List[dict] = []
    for e in events:
        node = dict(e, children=[], end_ns=e["start_ns"] + e["dur_ns"])
        while stack and node["start_ns"] >= stack[-1]["end_ns"]:
            stack.pop()
        (stack[-1]["children"] if stack else roots).append(node)
        stack.append(node)
    return roots


def _exclusive(node: dict) -> List[Interval]:
    """A span's own time: its interval less its children's."""
    out, cursor = [], node["start_ns"]
    for c in node["children"]:
        if c["start_ns"] > cursor:
            out.append((cursor, c["start_ns"]))
        cursor = max(cursor, c["end_ns"])
    if node["end_ns"] > cursor:
        out.append((cursor, node["end_ns"]))
    return out


def _walk(nodes: Sequence[dict]):
    for n in nodes:
        yield n
        yield from _walk(n["children"])


def _in_window(e: dict, w0: float, w1: float) -> bool:
    return w0 <= e["start_ns"] <= w1


def summarize(host: Sequence[dict], events: Sequence[dict], w0: float,
              w1: float) -> dict:
    """The numbers the module docstring lists, for the window [w0, w1] on
    the profiler's clock (ns)."""
    key = scheduler_line(host)
    line = _on_line(host, key)
    roots = _tree(line)
    ticks = [n for n in roots if n["name"] == TICK and _in_window(n, w0, w1)]
    window = w1 - w0
    tick_ivs = devtrace._union(_clip(
        [(n["start_ns"], n["end_ns"]) for n in roots if n["name"] == TICK],
        w0, w1))
    idle = device_idle(events, w0, w1)
    n_dev = max(1, len(idle))
    idle_all = sum(b - a for gaps in idle.values() for a, b in gaps) / n_dev
    idle_tick = sum(_overlap(gaps, tick_ivs) for gaps in idle.values()) / n_dev
    # the spans' own intervals are disjoint on one line: one sweep each
    own = sorted((a, b, n["name"]) for n in _walk(roots)
                 for a, b in _clip(_exclusive(n), w0, w1))
    by_span: Dict[str, float] = {}
    for gaps in idle.values():
        i = 0
        for a, b, name in own:
            while i < len(gaps) and gaps[i][1] <= a:
                i += 1
            j = i
            while j < len(gaps) and gaps[j][0] < b:
                t = min(b, gaps[j][1]) - max(a, gaps[j][0])
                by_span[name] = by_span.get(name, 0.0) + t / n_dev
                j += 1
    rest = idle_all - sum(by_span.values())
    if rest > 1.0:                  # ns: more than rounding
        by_span["unattributed"] = rest
    covered = devtrace._union(_clip(
        [(n["start_ns"], n["end_ns"]) for n in roots
         if n["name"] in (TICK, "scheduler.wait")], w0, w1))
    n_ticks = max(1, len(ticks))
    tick_ns = sum(n["dur_ns"] for n in ticks)
    child_ns = sum(c["dur_ns"] for n in ticks for c in n["children"])
    on_line = [n for n in _walk(roots) if _in_window(n, w0, w1)]

    def named(name: str) -> List[dict]:
        return [n for n in on_line if n["name"] == name]

    fuse = named("plan.fuse")
    rescore = named("dense.rescore")
    fronts = [e for e in host if e["name"] == "frontend"
              and _in_window(e, w0, w1)]
    responds = [e for e in host if e["name"] == "frontend.respond"
                and _in_window(e, w0, w1)]
    gcs = [e for e in host if e["name"] == "gc.pause"
           and _in_window(e, w0, w1)]
    ms = 1e-6
    out = {
        "scheduler_line": list(key),
        "ticks": len(ticks),
        "device_idle_pct": 100.0 * idle_all / window,
        "idle_in_tick_pct": 100.0 * idle_tick / window,
        "idle_between_ticks_pct": 100.0 * (idle_all - idle_tick) / window,
        "idle_ms_per_tick": {k: v * ms / n_ticks
                             for k, v in sorted(by_span.items(),
                                                key=lambda kv: -kv[1])},
        "tick_ms": tick_ns * ms / n_ticks,
        "wait_ms_per_tick": sum(n["dur_ns"] for n in named("device.wait"))
        * ms / n_ticks,
        "resolve_ms_per_tick": sum(n["dur_ns"]
                                   for n in named("scheduler.resolve"))
        * ms / n_ticks,
        "tick_wait_cover_pct": 100.0 * sum(b - a for a, b in covered)
        / window,
        "tick_children_pct": 100.0 * child_ns / tick_ns if tick_ns else None,
        "gc_pause_max_ms": max((e["dur_ns"] for e in gcs), default=0.0) * ms,
        "gc_pauses": len(gcs),
    }
    if fuse:
        out["fuse_ms"] = sum(n["dur_ns"] for n in fuse) * ms / len(fuse)
    if rescore:
        out["rescore_ms"] = sum(
            n["dur_ns"] - sum(c["dur_ns"] for c in _walk(n["children"])
                              if c["name"] == "device.wait")
            for n in rescore) * ms / len(rescore)
    if fronts:
        out["frontend_host_ms"] = (sum(e["dur_ns"] for e in fronts)
                                   + sum(e["dur_ns"] for e in responds)) \
            * ms / len(fronts)
    if responds:
        out["respond_ms"] = sum(e["dur_ns"] for e in responds) * ms \
            / len(responds)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import cell as cell_mod
    import spec
    from run import require_chips

    c = spec.resolve(args.workload)
    require_chips(c.chips)
    seen: dict = {}
    keep, reduce = devtrace.events_from_xplane, devtrace.reduce

    def keep_host(log_dir):
        seen["host"] = host_events(log_dir)
        return keep(log_dir)

    def reduce_and_note(events, window_unix, sync_unix, *a, **kw):
        seen.update(events=events, window_unix=window_unix,
                    sync_unix=sync_unix)
        return reduce(events, window_unix, sync_unix, *a, **kw)

    # The harness's own reduction runs unchanged; this only reads its
    # inputs.  `cell.run` has no hook for extra reductions, so the module
    # globals are replaced: this works only while `cell.py` calls them as
    # `trace_mod.events_from_xplane` and `trace_mod.reduce` (module
    # attributes, not names bound at import), and it fails loudly, at
    # `seen["events"]`, if it ever stops doing so.
    devtrace.events_from_xplane = keep_host
    devtrace.reduce = reduce_and_note
    out = cell_mod.run(c, args.seed, args.seconds, True, T_START)
    sync = [e for e in seen["events"] if e["name"] == devtrace.SYNC]
    offset = sync[0]["start_ns"] - seen["sync_unix"] * 1e9
    w0, w1 = (t * 1e9 + offset for t in seen["window_unix"])
    out["host"] = summarize(seen["host"], seen["events"], w0, w1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
