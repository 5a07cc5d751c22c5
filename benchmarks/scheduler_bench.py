"""Cross-client micro-batching scheduler benchmark.

Closed-loop multi-client load: C client threads each issue ONE retrieve at
a time, as fast as the service answers — the real deployment traffic shape
(SDK clients, server handlers, concurrent agents), which the positional
`retrieve_batch` API could never batch.  Two paths over the same data:

* **direct** — each call runs the full per-request pipeline alone (one
  embed, one masked search, one BM25 op, one fusion per CALL);
* **scheduled** — a mounted MemoryScheduler collects the concurrent
  clients' requests inside its micro-batch window and answers each tick
  with ONE batched launch per stage.

Reports throughput (requests/s) and per-request latency (p50/p99) for
each client count, plus the scheduled-vs-direct speedup.  The acceptance
bar from the PR: >= 2x throughput at 8 concurrent clients on CPU
(`--assert-speedup 2.0` enforces it in CI).

    PYTHONPATH=src python benchmarks/scheduler_bench.py \
        [--clients 1,2,4,8] [--seconds 2] [--tenants 8] \
        [--json BENCH_scheduler.json] [--assert-speedup 2.0]
"""
from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from repro.core import MemoryScheduler, MemoryService, Message
from repro.core.embedder import HashEmbedder

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


def _closed_loop(svc: MemoryService, clients: int, seconds: float) -> dict:
    """Each client thread retrieves in a closed loop for `seconds`;
    whether the call batches across clients is decided by whether a
    scheduler is mounted on `svc` (the client code is identical)."""
    lat: list[list[float]] = [[] for _ in range(clients)]
    stop = time.perf_counter() + seconds
    barrier = threading.Barrier(clients)

    def client(c: int) -> None:
        ns = f"u{c % len(svc.namespaces())}/c0"
        barrier.wait()
        i = 0
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            svc.retrieve(ns, QUERIES[i % len(QUERIES)])
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
    }


def run(clients=(1, 2, 4, 8), seconds: float = 2.0, tenants: int = 8,
        sessions: int = 2, tick_interval: float = 0.002,
        max_batch: int = 64, json_path=None,
        assert_speedup=None) -> dict:
    svc = _build_service(tenants, sessions)
    # warm every executable both paths touch (search buckets up to the
    # pow2 ceiling of the largest client count)
    for n in (1, 2, 4, 8, 16):
        if n <= max(clients) * 2:
            svc.retrieve_batch([(f"u{i % tenants}/c0", QUERIES[0])
                                for i in range(n)])
    print(f"# Scheduler bench: {tenants} tenants, "
          f"{svc.stats()['bank_rows']} bank rows, {seconds:.1f}s per point, "
          f"tick={tick_interval * 1e3:.1f}ms, max_batch={max_batch}")
    report = {"tenants": tenants, "seconds": seconds,
              "tick_interval_s": tick_interval, "max_batch": max_batch,
              "points": []}
    for c in clients:
        direct = _closed_loop(svc, c, seconds)
        sched = MemoryScheduler(svc, tick_interval_s=tick_interval,
                                max_batch=max_batch)
        try:
            scheduled = _closed_loop(svc, c, seconds)
            st = sched.stats()
        finally:
            sched.close()
        speedup = scheduled["throughput_rps"] / direct["throughput_rps"]
        point = {"clients": c, "direct": direct, "scheduled": scheduled,
                 "speedup": speedup,
                 "avg_batch": st.get("avg_retrieves_per_launch")}
        report["points"].append(point)
        print(f"clients {c:2d}: direct {direct['throughput_rps']:7.1f} rps "
              f"(p50 {direct['p50_ms']:.1f}ms p99 {direct['p99_ms']:.1f}ms)"
              f" | scheduled {scheduled['throughput_rps']:7.1f} rps "
              f"(p50 {scheduled['p50_ms']:.1f}ms p99 "
              f"{scheduled['p99_ms']:.1f}ms) | {speedup:.2f}x, "
              f"avg batch {point['avg_batch']:.1f}")
    top = report["points"][-1]
    report["speedup_at_max_clients"] = top["speedup"]
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    if assert_speedup is not None and top["speedup"] < assert_speedup:
        raise AssertionError(
            f"scheduled path is only {top['speedup']:.2f}x the direct path "
            f"at {top['clients']} clients (needed {assert_speedup:.2f}x)")
    return report


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", default="1,2,4,8",
                    help="comma-separated client counts")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=2)
    ap.add_argument("--tick-interval", type=float, default=0.002)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_scheduler.json artifact")
    ap.add_argument("--assert-speedup", type=float, default=None,
                    help="fail unless scheduled >= this x direct at the "
                         "largest client count")
    args = ap.parse_args()
    run(clients=tuple(int(x) for x in args.clients.split(",")),
        seconds=args.seconds, tenants=args.tenants, sessions=args.sessions,
        tick_interval=args.tick_interval, max_batch=args.max_batch,
        json_path=args.json, assert_speedup=args.assert_speedup)
