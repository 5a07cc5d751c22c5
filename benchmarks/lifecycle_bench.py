"""Lifecycle runtime soak benchmark.

Sustained ingest + retrieve with the WHOLE runtime live — background
flusher, bounded-queue backpressure, auto-compaction and snapshot rotation
all running against a durable directory — measuring what the lifecycle
subsystem actually promises:

* enqueue stays amortized O(1) for the client: p50/p99 per-enqueue latency
  while the daemon drains the queue behind it;
* retrieval keeps answering concurrently (p50/p99 per-batch latency);
* recovery is fast and *correct*: after the soak the directory is recovered
  (newest snapshot + WAL replay), timed, and the recovered service's
  answers are verified identical to the live one's.

    PYTHONPATH=src python benchmarks/lifecycle_bench.py \
        [--seconds 6] [--tenants 16] [--flush-interval 0.05] \
        [--max-pending 512] [--json BENCH_lifecycle.json]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro.core import LifecyclePolicy, MemoryService, Message
from repro.core.embedder import HashEmbedder

CITIES = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi", "Windhoek",
          "Sapporo"]
PETS = ["parrot", "gecko", "hedgehog", "magpie", "ferret", "otter"]


def _pcts(xs):
    if not xs:
        return {"p50_us": None, "p99_us": None, "mean_us": None}
    a = np.asarray(xs) * 1e6
    return {"p50_us": float(np.percentile(a, 50)),
            "p99_us": float(np.percentile(a, 99)),
            "mean_us": float(a.mean())}


def run(seconds: float = 6.0, tenants: int = 16,
        flush_interval: float = 0.05, max_pending: int = 512,
        snapshot_interval: float = 2.0, json_path=None,
        data_dir=None) -> dict:
    own_dir = data_dir is None
    data_dir = data_dir or tempfile.mkdtemp(prefix="memori-lifecycle-")
    policy = LifecyclePolicy(
        flush_interval_s=flush_interval, max_pending=max_pending,
        backpressure="block", compact_tombstone_ratio=0.2,
        compact_min_tombstones=8, compact_idle_s=0.0,
        snapshot_interval_s=snapshot_interval, snapshot_retain=2,
        tick_s=0.01)
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800,
                        policy=policy, data_dir=os.path.join(data_dir, "d"))
    print(f"# Lifecycle soak: {seconds:.0f}s, {tenants} tenants, "
          f"flush_interval={flush_interval}s, max_pending={max_pending}, "
          f"snapshot_interval={snapshot_interval}s")
    enq_lat, ret_lat = [], []
    i, t_end = 0, time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        ns = f"u{i % tenants}/c0"
        msgs = [Message("U", f"I live in {CITIES[i % len(CITIES)]}.",
                        1700000000.0 + i),
                Message("U", f"I adopted a {PETS[i % len(PETS)]} named "
                        f"N{i}.", 1700000000.0 + i)]
        t0 = time.perf_counter()
        svc.enqueue(ns, f"s{i}", msgs)
        enq_lat.append(time.perf_counter() - t0)
        if i % 16 == 15:             # interleaved reads (flush + search)
            batch = [(f"u{j % tenants}/c0",
                      "Which city does the user live in?")
                     for j in range(i, i + 4)]
            t0 = time.perf_counter()
            svc.retrieve_batch(batch)
            ret_lat.append(time.perf_counter() - t0)
        if i % 64 == 63:             # churn for the auto-compactor
            svc.evict(f"u{i % tenants}/c0")
        i += 1
    st = svc.stats()
    live_answers = [c.text for c in svc.retrieve_batch(
        [(f"u{j}/c0", "Which city does the user live in?")
         for j in range(tenants)])]
    # handoff without a final snapshot: recovery must work from whatever
    # the runtime had made durable plus the final flush segment.  Stop the
    # daemon first — recovery may not race a live writer's rotation (a
    # directory has one writer at a time; see docs/OPERATIONS.md)
    svc.close(final_snapshot=False)
    rt_stats = st["lifecycle"]
    t0 = time.perf_counter()
    recovered = MemoryService.recover(os.path.join(data_dir, "d"),
                                      HashEmbedder(), use_kernel=False,
                                      budget=800)
    t_recover = time.perf_counter() - t0
    rec_answers = [c.text for c in recovered.retrieve_batch(
        [(f"u{j}/c0", "Which city does the user live in?")
         for j in range(tenants)])]
    identical = rec_answers == live_answers
    report = {
        "seconds": seconds, "tenants": tenants,
        "sessions_enqueued": i,
        "enqueue": _pcts(enq_lat),
        "retrieve_batch4": _pcts(ret_lat),
        "flushes": rt_stats["flushes"],
        "auto_compactions": rt_stats["auto_compactions"],
        "rotations": rt_stats["rotations"],
        "wal_segments_at_end": st["wal_segments"],
        "bank_rows": st["bank_rows"],
        "recovery_s": t_recover,
        "recovered_identical": identical,
    }
    print(f"sessions {i}: enqueue p50 {report['enqueue']['p50_us']:.0f}us "
          f"p99 {report['enqueue']['p99_us']:.0f}us | retrieve(B=4) p50 "
          f"{report['retrieve_batch4']['p50_us']:.0f}us | flushes "
          f"{report['flushes']}, compactions {report['auto_compactions']}, "
          f"rotations {report['rotations']}")
    print(f"recovery: {t_recover*1e3:.0f}ms for {st['bank_rows']} rows, "
          f"identical={identical}")
    if not identical:
        raise AssertionError("recovered service diverged from the live one")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    if own_dir:
        shutil.rmtree(data_dir, ignore_errors=True)
    return report


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--tenants", type=int, default=16)
    ap.add_argument("--flush-interval", type=float, default=0.05)
    ap.add_argument("--max-pending", type=int, default=512)
    ap.add_argument("--snapshot-interval", type=float, default=2.0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_lifecycle.json artifact")
    args = ap.parse_args()
    run(seconds=args.seconds, tenants=args.tenants,
        flush_interval=args.flush_interval, max_pending=args.max_pending,
        snapshot_interval=args.snapshot_interval, json_path=args.json)
