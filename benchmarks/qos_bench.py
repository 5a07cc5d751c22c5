"""Per-tenant QoS benchmark: one abusive tenant vs a fleet of well-behaved
closed-loop clients.

Two phases over the same data and the same scheduler policy:

* **baseline** — C well-behaved clients (spread over T tenants), each
  submitting one retrieve at a time in a closed loop;
* **abuse** — the same fleet, plus one abusive tenant firing large
  `submit_many` blocks asynchronously as fast as admission lets it (never
  waiting for results — the open-loop flood shape that starved everyone
  under the PR-5 FIFO drain).

The number that matters is **protection**: the well-behaved fleet's p99
under abuse divided by its baseline p99.  Under FIFO the abuser's backlog
sat in front of every tick and the ratio exploded with flood depth; with
admission control (WRR slots per tick + per-tenant queue cap shedding the
flood) it must stay small.  `--assert-protection 2.0` enforces the PR's
acceptance bar — well-behaved p99 degrades < 2x — and CI gates on it.

    PYTHONPATH=src python benchmarks/qos_bench.py \
        [--clients 100] [--tenants 20] [--seconds 3] \
        [--abuse-block 64] [--max-batch 256] \
        [--json BENCH_qos.json] [--assert-protection 2.0]
"""
from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from repro.core import (AdmissionError, AdmissionPolicy, MemoryScheduler,
                        MemoryService, Message, RetrieveRequest, TenantPolicy)
from repro.core.embedder import HashEmbedder

CITIES = ["Tallinn", "Porto", "Cusco", "Oslo", "Quito", "Hanoi", "Windhoek",
          "Sapporo"]
QUERIES = ["Which city does the user live in?",
           "What pet was adopted?",
           "What is the user's job?"]
ABUSER = "abuser"


def _build_service(tenants: int) -> MemoryService:
    svc = MemoryService(HashEmbedder(), use_kernel=False, budget=800)
    for u in range(tenants):
        svc.record(f"w{u}/c0", "s0", [
            Message("U", f"I live in {CITIES[u % len(CITIES)]}.",
                    1700000000.0),
            Message("U", f"I adopted a pet named P{u}.", 1700000000.0),
            Message("U", "I work as a welder.", 1700000000.0)])
    svc.record(f"{ABUSER}/c0", "s0", [
        Message("U", "I live in Flood City.", 1700000000.0)])
    return svc


def _policy(max_batch: int) -> AdmissionPolicy:
    """One uniform contract for everyone — the abuser gets no special
    treatment, which is the point: fairness must come from the mechanism,
    not from hand-tuning the attacker."""
    return AdmissionPolicy(
        default=TenantPolicy(max_queued=4 * max_batch),
        shed_retry_after_s=0.05)


def _well_behaved_phase(sched: MemoryScheduler, clients: int, tenants: int,
                        seconds: float, abuse_block: int = 0) -> dict:
    lat: list[list[float]] = [[] for _ in range(clients)]
    errors = [0]
    abuse = {"submitted": 0, "shed": 0}
    stop_at = time.perf_counter() + seconds
    parties = clients + (1 if abuse_block else 0)
    barrier = threading.Barrier(parties)

    def client(c: int) -> None:
        req = RetrieveRequest(f"w{c % tenants}/c0",
                              QUERIES[c % len(QUERIES)])
        barrier.wait()
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            try:
                resp = sched.submit(req).result(timeout=60)
                if not resp.ok:
                    errors[0] += 1
                    continue
            except AdmissionError:
                # well-behaved tenants should essentially never be shed;
                # count it as an error so the report surfaces it
                errors[0] += 1
                time.sleep(0.01)
                continue
            lat[c].append(time.perf_counter() - t0)

    def abuser() -> None:
        block = [RetrieveRequest(f"{ABUSER}/c0", QUERIES[0])] * abuse_block
        barrier.wait()
        while time.perf_counter() < stop_at:
            try:
                sched.submit_many(block, tenant=ABUSER)
                abuse["submitted"] += abuse_block
            except AdmissionError as e:
                abuse["shed"] += abuse_block
                # the flood ignores most of the retry hint — that is what
                # makes it abusive — but yields the GIL so the bench
                # measures scheduling policy, not lock spin
                time.sleep(min(0.001, e.retry_after_s))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    if abuse_block:
        threads.append(threading.Thread(target=abuser))
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    # drain whatever the abuser left queued so the next phase starts clean
    while sched.admission.total_queued:
        time.sleep(0.01)
    flat = np.asarray([x for per in lat for x in per])
    out = {
        "requests": int(flat.size),
        "throughput_rps": float(flat.size / wall),
        "p50_ms": float(np.percentile(flat, 50) * 1e3),
        "p99_ms": float(np.percentile(flat, 99) * 1e3),
        "errors": errors[0],
    }
    if abuse_block:
        out["abuser"] = dict(abuse)
    return out


def run(clients: int = 100, tenants: int = 20, seconds: float = 3.0,
        abuse_block: int = 64, tick_interval: float = 0.002,
        max_batch: int = 256, json_path=None,
        assert_protection=None) -> dict:
    svc = _build_service(tenants)
    # warm every pow2 search bucket a tick can reach, so p99 measures the
    # scheduling policy and not one-off jit compiles mid-phase
    n = 1
    while n <= max_batch:
        svc.retrieve_batch([(f"w{i % tenants}/c0", QUERIES[0])
                            for i in range(n)])
        n *= 2
    print(f"# QoS bench: {clients} well-behaved clients over {tenants} "
          f"tenants + 1 abusive tenant ({abuse_block}-request async "
          f"blocks), {seconds:.1f}s per phase, max_batch={max_batch}")
    report = {"clients": clients, "tenants": tenants, "seconds": seconds,
              "abuse_block": abuse_block, "max_batch": max_batch}

    sched = MemoryScheduler(svc, tick_interval_s=tick_interval,
                            max_batch=max_batch,
                            admission=_policy(max_batch))
    try:
        baseline = _well_behaved_phase(sched, clients, tenants, seconds)
        abused = _well_behaved_phase(sched, clients, tenants, seconds,
                                     abuse_block=abuse_block)
        st = sched.stats()
    finally:
        sched.close()
    protection = abused["p99_ms"] / baseline["p99_ms"]
    report.update(baseline=baseline, under_abuse=abused,
                  p99_degradation=protection,
                  admission=st["admission"],
                  avg_batch=st.get("avg_retrieves_per_launch"))
    print(f"baseline    : {baseline['throughput_rps']:8.1f} rps  "
          f"p50 {baseline['p50_ms']:6.1f}ms  p99 {baseline['p99_ms']:6.1f}ms")
    print(f"under abuse : {abused['throughput_rps']:8.1f} rps  "
          f"p50 {abused['p50_ms']:6.1f}ms  p99 {abused['p99_ms']:6.1f}ms  "
          f"(abuser admitted {abused['abuser']['submitted']}, "
          f"shed {abused['abuser']['shed']})")
    print(f"well-behaved p99 degradation under abuse: {protection:.2f}x "
          f"(errors: {baseline['errors']}/{abused['errors']})")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    if assert_protection is not None and protection > assert_protection:
        raise AssertionError(
            f"one abusive tenant degraded well-behaved p99 by "
            f"{protection:.2f}x (bar: < {assert_protection:.2f}x) — "
            "admission control is not protecting the fleet")
    return report


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=100,
                    help="well-behaved closed-loop client threads")
    ap.add_argument("--tenants", type=int, default=20,
                    help="tenants the well-behaved clients spread over")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--abuse-block", type=int, default=64,
                    help="requests per async abuser submit_many block")
    ap.add_argument("--tick-interval", type=float, default=0.002)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_qos.json artifact")
    ap.add_argument("--assert-protection", type=float, default=None,
                    help="fail if well-behaved p99 under abuse exceeds "
                         "this multiple of its no-abuser baseline")
    args = ap.parse_args()
    run(clients=args.clients, tenants=args.tenants, seconds=args.seconds,
        abuse_block=args.abuse_block, tick_interval=args.tick_interval,
        max_batch=args.max_batch, json_path=args.json,
        assert_protection=args.assert_protection)
