"""Kill-a-shard recovery soak benchmark.

Sustained ingest + retrieve against a SHARDED store with a durable
directory and a follower sink attached (every sealed WAL segment — shard
logs included — streams to the follower), then the failure drill the
replication layer exists for:

* **degraded-mode availability**: with one shard marked down, what
  fraction of a full-fleet retrieval batch still answers with data (the
  survivors must be bit-identical to the healthy baseline, the victims
  flagged `degraded`, and nothing may fail);
* **recovery time**: lose the shard's disk outright (`rm -rf shard-01/`),
  re-materialize it from the follower's shipped segments, and recover —
  timed end to end;
* **the correctness gate**: the recovered service must answer
  bit-identically to the live one (per-tenant retrieval texts AND the
  sha256 of the bank-row prefix).  CI fails on any divergence.

    PYTHONPATH=src python benchmarks/shard_recovery_bench.py \
        [--seconds 4] [--shards 2] [--tenants 8] \
        [--json BENCH_shard_recovery.json]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro.checkpoint.replication import (DirectorySink,
                                          restore_missing_from_follower)
from repro.core import MemoryService, Message
from repro.core.embedder import HashEmbedder

CITIES = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi", "Windhoek",
          "Sapporo"]
PETS = ["parrot", "gecko", "hedgehog", "magpie", "ferret", "otter"]
QUERY = "Which city does the user live in?"


def _pcts(xs):
    if not xs:
        return {"p50_us": None, "p99_us": None}
    a = np.asarray(xs) * 1e6
    return {"p50_us": float(np.percentile(a, 50)),
            "p99_us": float(np.percentile(a, 99))}


def _bank_sha(svc, rows=None):
    bank = np.ascontiguousarray(
        svc.vindex.bank if rows is None else svc.vindex.bank[:rows])
    return hashlib.sha256(bank.tobytes()).hexdigest()


def run(seconds: float = 4.0, shards: int = 2, tenants: int = 8,
        json_path=None, data_dir=None) -> dict:
    own_dir = data_dir is None
    root = data_dir or tempfile.mkdtemp(prefix="memori-shardrec-")
    d = os.path.join(root, "data")
    follower = os.path.join(root, "follower")
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800,
                        shards=shards, data_dir=d)
    svc.attach_follower(follower)             # sync: RPO = 0 segments
    print(f"# Shard recovery soak: {seconds:.0f}s, shards={shards}, "
          f"tenants={tenants}, follower={follower}")

    # -- soak: flush-per-session ingest with interleaved reads -------------
    i, t_end = 0, time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        ns = f"u{i % tenants}/c0"
        svc.enqueue(ns, f"s{i}", [
            Message("U", f"I live in {CITIES[i % len(CITIES)]}.",
                    1700000000.0 + i),
            Message("U", f"I adopted a {PETS[i % len(PETS)]} named N{i}.",
                    1700000000.0 + i)])
        svc.flush()          # durable: shard parts + cross-shard commit
        if i == 2:
            svc.rotate()     # one mid-soak snapshot generation
        i += 1
    queries = [(f"u{j}/c0", QUERY) for j in range(tenants)]
    live = [c.text for c in svc.retrieve_batch(queries)]
    bank_rows = int(svc.vindex.n)
    live_sha = _bank_sha(svc)
    shipped = svc.stats().get("replication") or {}

    # -- degraded mode: one shard down, survivors keep answering -----------
    down = 1 % shards
    victims = [j for j in range(tenants)
               if svc.store.shard_of_namespace(f"u{j}/c0") == down]
    svc.set_shard_down(down)
    deg_lat, answered, flagged = [], 0, 0
    for _ in range(20):
        t0 = time.perf_counter()
        got = svc.retrieve_batch(queries)
        deg_lat.append(time.perf_counter() - t0)
        for j, c in enumerate(got):
            if c.degraded:
                flagged += 1
            else:
                answered += 1
                if c.text != live[j]:
                    raise AssertionError(
                        f"survivor u{j} diverged in degraded mode")
    total = 20 * tenants
    availability = answered / total
    assert flagged == 20 * len(victims), "degraded flags != downed tenants"
    svc.set_shard_up(down)
    print(f"degraded mode: {availability:.0%} of requests answered with "
          f"shard {down} down ({len(victims)}/{tenants} tenants flagged), "
          f"batch p50 {_pcts(deg_lat)['p50_us']:.0f}us")

    # -- kill the shard's disk, restore from follower, recover -------------
    svc.close(final_snapshot=False)
    shard_dir = os.path.join(d, f"shard-{down:02d}")
    shutil.rmtree(shard_dir)
    t0 = time.perf_counter()
    restored = restore_missing_from_follower(DirectorySink(follower), d)
    t_restore = time.perf_counter() - t0
    t0 = time.perf_counter()
    recovered = MemoryService.recover(d, HashEmbedder(), use_kernel=False,
                                      budget=800)
    t_recover = time.perf_counter() - t0
    rec = [c.text for c in recovered.retrieve_batch(queries)]
    texts_identical = rec == live
    bank_identical = (int(recovered.vindex.n) == bank_rows
                      and _bank_sha(recovered) == live_sha)
    recovered.close(final_snapshot=False)

    report = {
        "seconds": seconds, "shards": shards, "tenants": tenants,
        "sessions_flushed": i, "bank_rows": bank_rows,
        "segments_shipped": shipped.get("shipped"),
        "ship_failures": shipped.get("failed"),
        "degraded_availability": availability,
        "degraded_batch": _pcts(deg_lat),
        "restore_files": len(restored),
        "restore_s": t_restore,
        "recovery_s": t_recover,
        "recovered_texts_identical": texts_identical,
        "recovered_bank_identical": bank_identical,
    }
    print(f"sessions {i}, bank rows {bank_rows}: shipped "
          f"{shipped.get('shipped')} segments ({shipped.get('failed')} "
          f"failed)")
    print(f"recovery: restored {len(restored)} files from follower in "
          f"{t_restore*1e3:.0f}ms, recovered in {t_recover*1e3:.0f}ms, "
          f"texts_identical={texts_identical} "
          f"bank_identical={bank_identical}")
    if not (texts_identical and bank_identical):
        raise AssertionError(
            "recovered service diverged from the live one after "
            "kill-a-shard recovery")
    if shipped.get("failed"):
        raise AssertionError(f"{shipped['failed']} WAL segments failed to "
                             "ship during the soak")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    if own_dir:
        shutil.rmtree(root, ignore_errors=True)
    return report


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_shard_recovery.json artifact")
    args = ap.parse_args()
    run(seconds=args.seconds, shards=args.shards, tenants=args.tenants,
        json_path=args.json)
