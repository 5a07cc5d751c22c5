"""Multi-tenant MemoryService throughput: the tentpole metrics of the
storage engine.

* retrieval — batched vs sequential: N tenants each hold a few ingested
  sessions in one packed bank; a batch of per-tenant queries is answered
  either as N sequential `retrieve` calls (N embed calls + N top-k
  launches) or as ONE `retrieve_batch` (one embed call + one
  namespace-masked topk_mips launch + one stacked BM25 scoring op).
* ingestion — batched vs sequential: B sessions ingested either as B
  synchronous `record` calls (B embed calls + B bank appends) or enqueued
  and drained by ONE `flush()` (one embed call + one bank append).
* compaction — tombstone half the bank, time `compact()`, report the
  reclaimed rows.

Wall-clock here is CPU (kernel off by default — Pallas interpret mode would
time the emulator, not the algorithm); on TPU the batched paths additionally
amortize kernel launch + HBM bank streaming across the whole batch.

    PYTHONPATH=src python benchmarks/service_throughput.py [--kernel]
        [--mode retrieve|ingest|compact|all] [--tenants N] [--sessions S]
        [--batches 1,8,32] [--json BENCH_service.json]
"""
from __future__ import annotations

import json
import time

from repro.core.extraction import Message
from repro.core.service import MemoryService
from repro.core.embedder import HashEmbedder

BATCH_SIZES = (1, 8, 32)
N_TENANTS = 32
SESSIONS_PER_TENANT = 3

FACTS = [
    "I work as a {job} and I live in {city}.",
    "I adopted a {pet} named {name}.",
    "My favorite color is {color}.",
]
JOBS = ["botanist", "welder", "pilot", "baker", "cartographer", "luthier"]
CITIES = ["tallinn", "porto", "cusco", "sapporo", "tromso", "windhoek"]
PETS = ["hedgehog", "parrot", "gecko", "ferret", "axolotl", "magpie"]
NAMES = ["biscuit", "olive", "comet", "pickle", "juniper", "maple"]
COLORS = ["indigo", "ochre", "teal", "crimson", "sage", "amber"]


def _sessions(n_tenants: int, per_tenant: int):
    out = []
    for u in range(n_tenants):
        ns = f"user{u}/c0"
        for s in range(per_tenant):
            texts = [f.format(job=JOBS[(u + s) % len(JOBS)],
                              city=CITIES[(u + s) % len(CITIES)],
                              pet=PETS[(u + s) % len(PETS)],
                              name=NAMES[(u + s) % len(NAMES)],
                              color=COLORS[(u + s) % len(COLORS)])
                     for f in FACTS]
            msgs = [Message(f"user{u}", t, 1700000000.0 + s) for t in texts]
            out.append((ns, f"s{s}", msgs))
    return out


def _build_service(use_kernel: bool, n_tenants: int = N_TENANTS,
                   per_tenant: int = SESSIONS_PER_TENANT) -> MemoryService:
    svc = MemoryService(HashEmbedder(), budget=800, use_kernel=use_kernel)
    for ns, sid, msgs in _sessions(n_tenants, per_tenant):
        svc.record(ns, sid, msgs)
    return svc


def _time(fn, iters: int = 5) -> float:
    fn()                       # warmup (jit caches, lazy arrays)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def run_retrieval(csv_rows, use_kernel: bool = False,
                  n_tenants: int = N_TENANTS,
                  per_tenant: int = SESSIONS_PER_TENANT,
                  batches=BATCH_SIZES, json_out=None):
    print("\n# MemoryService throughput — batched vs sequential retrieval"
          + (" [pallas kernel]" if use_kernel else " [jnp ref path]"))
    svc = _build_service(use_kernel, n_tenants, per_tenant)
    queries = [(f"user{u}/c0", f"Which city does user{u} live in?")
               for u in range(n_tenants)]
    for B in dict.fromkeys(min(b, len(queries)) for b in batches):
        batch = queries[:B]
        t_seq = _time(lambda: [svc.retrieve(ns, q) for ns, q in batch])
        t_bat = _time(lambda: svc.retrieve_batch(batch))
        speedup = t_seq / t_bat
        qps_seq = B / t_seq
        qps_bat = B / t_bat
        print(f"batch {B:3d}: sequential {t_seq*1e3:8.1f}ms ({qps_seq:7.1f} q/s)"
              f" | batched {t_bat*1e3:8.1f}ms ({qps_bat:7.1f} q/s)"
              f" | speedup {speedup:5.2f}x")
        csv_rows.append((f"service/batch{B}", t_bat * 1e6,
                         f"{speedup:.2f}x vs sequential"))
        if json_out is not None:
            json_out.append({"batch": B, "t_seq_ms": t_seq * 1e3,
                             "t_batched_ms": t_bat * 1e3,
                             "speedup": speedup})
    return csv_rows


def run_ingest(csv_rows, use_kernel: bool = False,
               n_tenants: int = N_TENANTS,
               per_tenant: int = SESSIONS_PER_TENANT,
               batches=BATCH_SIZES, json_out=None):
    print("\n# MemoryService throughput — batched (enqueue+flush) vs "
          "sequential (record) ingestion")
    sessions = _sessions(n_tenants, per_tenant)
    for B in dict.fromkeys(min(b, len(sessions)) for b in batches):
        batch = sessions[:B]

        def seq():
            svc = MemoryService(HashEmbedder(), budget=800,
                                use_kernel=use_kernel)
            for ns, sid, msgs in batch:
                svc.record(ns, sid, msgs)

        def bat():
            svc = MemoryService(HashEmbedder(), budget=800,
                                use_kernel=use_kernel)
            for ns, sid, msgs in batch:
                svc.enqueue(ns, sid, msgs)
            svc.flush()

        t_seq = _time(seq, iters=3)
        t_bat = _time(bat, iters=3)
        speedup = t_seq / t_bat
        print(f"batch {B:3d}: sequential {t_seq*1e3:8.1f}ms "
              f"({B/t_seq:7.1f} sess/s) | batched {t_bat*1e3:8.1f}ms "
              f"({B/t_bat:7.1f} sess/s) | speedup {speedup:5.2f}x")
        csv_rows.append((f"service/ingest{B}", t_bat * 1e6,
                         f"{speedup:.2f}x vs sequential record"))
        if json_out is not None:
            json_out.append({"batch": B, "t_seq_ms": t_seq * 1e3,
                             "t_batched_ms": t_bat * 1e3,
                             "speedup": speedup})
    return csv_rows


def run_compact(csv_rows, use_kernel: bool = False,
                n_tenants: int = N_TENANTS,
                per_tenant: int = SESSIONS_PER_TENANT, json_out=None):
    print("\n# MemoryService — bank compaction (tombstone reclamation)")
    svc = _build_service(use_kernel, n_tenants, per_tenant)
    for u in range(0, n_tenants, 2):      # evict every other tenant
        svc.evict(f"user{u}/c0")
    st = svc.stats()
    t0 = time.perf_counter()
    info = svc.compact()
    dt = time.perf_counter() - t0
    print(f"compact: {info['rows_before']} -> {info['rows_after']} rows "
          f"({info['dropped']} reclaimed, {st['tombstones']} tombstones) "
          f"in {dt*1e3:.1f}ms")
    csv_rows.append(("service/compact", dt * 1e6,
                     f"{info['dropped']} rows reclaimed"))
    if json_out is not None:
        json_out.update({"t_ms": dt * 1e3, **info})
    return csv_rows


def run(csv_rows, use_kernel: bool = False, mode: str = "all",
        n_tenants: int = N_TENANTS, per_tenant: int = SESSIONS_PER_TENANT,
        batches=BATCH_SIZES, json_path=None):
    report = {"retrieval": [], "ingestion": [], "compaction": {}}
    if mode in ("retrieve", "all"):
        run_retrieval(csv_rows, use_kernel, n_tenants, per_tenant, batches,
                      json_out=report["retrieval"])
    if mode in ("ingest", "all"):
        run_ingest(csv_rows, use_kernel, n_tenants, per_tenant, batches,
                   json_out=report["ingestion"])
    if mode in ("compact", "all"):
        run_compact(csv_rows, use_kernel, n_tenants, per_tenant,
                    json_out=report["compaction"])
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"\nwrote {json_path}")
    return csv_rows


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", action="store_true",
                    help="route dense search through the Pallas kernel "
                         "(interpret mode off-TPU: slow, for parity checks)")
    ap.add_argument("--mode", default="all",
                    choices=["retrieve", "ingest", "compact", "all"])
    ap.add_argument("--tenants", type=int, default=N_TENANTS)
    ap.add_argument("--sessions", type=int, default=SESSIONS_PER_TENANT)
    ap.add_argument("--batches", default=",".join(map(str, BATCH_SIZES)),
                    help="comma-separated batch sizes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_service.json artifact")
    args = ap.parse_args()
    run([], use_kernel=args.kernel, mode=args.mode, n_tenants=args.tenants,
        per_tenant=args.sessions,
        batches=tuple(int(b) for b in args.batches.split(",")),
        json_path=args.json)
