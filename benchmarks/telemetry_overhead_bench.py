"""Telemetry overhead gate (PR 9): instrumentation must be ~free.

Closed-loop multi-client load over the scheduled retrieve path — the same
traffic shape as scheduler_bench — run twice per phase pair with the ONLY
difference being the process-wide telemetry registry: `enabled=False`
(every entry point a no-op — the uninstrumented baseline) vs
`enabled=True` with a live per-request trace, exactly what the HTTP
frontend does (start_trace -> activate -> submit with the trace ->
finish), so every measured request pays for its span tree (queue wait,
shared tick, every plan stage), the latency histograms and the counters.

Phases interleave OFF/ON `--pairs` times, alternating within-pair order.
The gated statistic is the MEDIAN of the within-pair p50 ratios: the two
phases of a pair run back to back under the same machine conditions, so
their ratio isolates the telemetry cost even when absolute latency
drifts several percent across the run (pooled or per-mode medians do
not — on a shared box the drift is larger than the effect).  The CI bar
from the PR: telemetry adds < 5% to p50 (`--assert-overhead 1.05`).

    PYTHONPATH=src python benchmarks/telemetry_overhead_bench.py \
        [--clients 4] [--seconds 0.5] [--pairs 10] \
        [--json BENCH_telemetry.json] [--assert-overhead 1.05]
"""
from __future__ import annotations

import argparse
import json
import statistics
import threading
import time

import numpy as np

from repro.core import MemoryScheduler, MemoryService, Message
from repro.core.api import RetrieveRequest
from repro.core.embedder import HashEmbedder
from repro.obs.telemetry import Telemetry, get_telemetry, set_telemetry

CITIES = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi", "Windhoek",
          "Sapporo"]
QUERIES = ["Which city does the user live in?",
           "What pet was adopted?",
           "What is the user's job?"]


def _build_service(tenants: int, sessions: int) -> MemoryService:
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800)
    for u in range(tenants):
        for s in range(sessions):
            svc.record(f"u{u}/c0", f"s{s}", [
                Message("U", f"I live in {CITIES[(u + s) % len(CITIES)]}.",
                        1700000000.0 + s),
                Message("U", f"I adopted a pet named P{u}_{s}.",
                        1700000000.0 + s),
                Message("U", "I work as a welder.", 1700000000.0 + s)])
    return svc


def _closed_loop(sched: MemoryScheduler, tenants: int, clients: int,
                 seconds: float) -> dict:
    """Each client thread runs one traced retrieve at a time, the way the
    HTTP frontend drives the scheduler.  With telemetry disabled,
    start_trace returns None and the whole ceremony collapses to no-ops —
    the two modes run byte-identical client code."""
    lat: list[list[float]] = [[] for _ in range(clients)]
    stop = time.perf_counter() + seconds
    barrier = threading.Barrier(clients)

    def client(c: int) -> None:
        tel = get_telemetry()
        ns = f"u{c % tenants}/c0"
        barrier.wait()
        i = 0
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            tr = tel.start_trace(op="retrieve")
            req = RetrieveRequest(namespace=ns,
                                  query=QUERIES[i % len(QUERIES)])
            with tel.activate([tr]):
                fut = sched.submit_many([req], traces=[tr])[0]
            fut.result(timeout=60)
            tel.finish_trace(tr)
            lat[c].append(time.perf_counter() - t0)
            i += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = np.asarray([x for per in lat for x in per])
    return {
        "requests": int(flat.size),
        "throughput_rps": float(flat.size / wall),
        "p50_ms": float(np.percentile(flat, 50) * 1e3),
        "p99_ms": float(np.percentile(flat, 99) * 1e3),
    }, flat


def run(clients: int = 4, seconds: float = 0.5, pairs: int = 10,
        tenants: int = 8, sessions: int = 2, tick_interval: float = 0.002,
        max_batch: int = 64, json_path=None, assert_overhead=None) -> dict:
    prev_tel = get_telemetry()
    svc = _build_service(tenants, sessions)
    sched = MemoryScheduler(svc, tick_interval_s=tick_interval,
                            max_batch=max_batch)
    print(f"# Telemetry overhead bench: {clients} clients, "
          f"{pairs} interleaved off/on pairs, {seconds:.1f}s per phase, "
          f"{svc.stats()['bank_rows']} bank rows")
    report = {"clients": clients, "seconds": seconds, "pairs": pairs,
              "tenants": tenants, "phases": []}
    ratios_p50: list[float] = []
    ratios_rps: list[float] = []
    try:
        # warm executables + scheduler once, instrumented (worst case)
        set_telemetry(Telemetry())
        _closed_loop(sched, tenants, clients, min(seconds, 0.5))
        for pair in range(pairs):
            # alternate within-pair order: a systematic first/second-phase
            # effect (cache state, GC debt from the previous phase) would
            # otherwise bias one mode
            order = ("off", "on") if pair % 2 == 0 else ("on", "off")
            by_mode = {}
            for mode in order:
                set_telemetry(Telemetry(enabled=(mode == "on")))
                point, _ = _closed_loop(sched, tenants, clients, seconds)
                point["mode"] = mode
                by_mode[mode] = point
                report["phases"].append(point)
                print(f"pair {pair} {mode:>3}: "
                      f"{point['throughput_rps']:7.1f} rps  "
                      f"p50 {point['p50_ms']:.3f}ms  "
                      f"p99 {point['p99_ms']:.3f}ms")
            ratios_p50.append(by_mode["on"]["p50_ms"]
                              / by_mode["off"]["p50_ms"])
            ratios_rps.append(by_mode["on"]["throughput_rps"]
                              / by_mode["off"]["throughput_rps"])
    finally:
        sched.close()
        set_telemetry(prev_tel)
    report["pair_p50_ratios"] = ratios_p50
    report["overhead_p50"] = statistics.median(ratios_p50)
    report["throughput_ratio"] = statistics.median(ratios_rps)
    print(f"per-pair p50 ratios: "
          f"{', '.join(f'{r:.3f}' for r in ratios_p50)}")
    print(f"overhead {report['overhead_p50']:.4f}x p50 "
          f"(throughput ratio {report['throughput_ratio']:.4f})")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    if assert_overhead is not None \
            and report["overhead_p50"] > assert_overhead:
        raise AssertionError(
            f"telemetry costs {report['overhead_p50']:.4f}x the disabled "
            f"baseline p50 (gate: {assert_overhead:.2f}x)")
    return report


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=0.5,
                    help="per-phase duration")
    ap.add_argument("--pairs", type=int, default=10,
                    help="interleaved off/on phase pairs")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=2)
    ap.add_argument("--tick-interval", type=float, default=0.002)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_telemetry.json artifact")
    ap.add_argument("--assert-overhead", type=float, default=None,
                    help="fail if instrumented p50 exceeds this x the "
                         "disabled-telemetry p50")
    args = ap.parse_args()
    run(clients=args.clients, seconds=args.seconds, pairs=args.pairs,
        tenants=args.tenants, sessions=args.sessions,
        tick_interval=args.tick_interval, max_batch=args.max_batch,
        json_path=args.json, assert_overhead=args.assert_overhead)
