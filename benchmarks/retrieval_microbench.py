"""Retrieval hot-path microbenchmark.

Two modes:

* quick (default; what `benchmarks/run.py` invokes): the original
  kernel-vs-oracle wall-clock rows on growing bank sizes plus the v5e
  roofline terms (CPU wall-clock is indicative only — EXPERIMENTS.md
  §Roofline has the TPU numbers).

* steady (`--steady`): the device-resident engine acceptance benchmark.
  A bank of `--rows` rows is grown one append at a time while a batch of
  tenant queries is answered after every append — the serving pattern.
  Two implementations of the same read path are timed (warmup first, then
  `block_until_ready` timing):

    - host-roundtrip: the pre-engine code path, faithfully preserved —
      host numpy bank, per-call `jnp.asarray(bank)` upload, per-call
      row-namespace rebuild from a Python list, eager masked-oracle
      scoring;
    - device-resident: `VectorIndex.search_batch` — capacity-padded device
      buffers updated in place, cached device labels, one stable-shape
      jitted launch with the live-row count as a traced scalar.

  A compile counter (jax_log_compiles capture) runs over the growth window
  and the benchmark ASSERTS zero recompiles for the device path while the
  bank grows within one power-of-two capacity bucket.

    PYTHONPATH=src python benchmarks/retrieval_microbench.py --steady
        [--rows 65000] [--batch 8] [--iters 5] [--json BENCH_retrieval.json]

* quantized (`--quantized`): the int8-bank acceptance benchmark.  The same
  >= 64k-row steady-state serving pattern is timed twice — f32 residency
  vs int8 codes + per-row scales with the exact-f32 rescore — and the
  benchmark reports (a) steady-state latency for both, (b) the bank bytes
  READ per search (the scan is bandwidth-bound, so this is the term the
  quantized kernel shrinks; ASSERTED >= 2x lower including the rescore
  gather), and (c) measured recall@k of the quantized index against the
  exact f32 oracle (`--assert-recall 0.95` gates it in CI).

    PYTHONPATH=src python benchmarks/retrieval_microbench.py --quantized
        [--rows 65000] [--k 10] [--assert-recall 0.95]
        [--json BENCH_quantized.json]
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import count_compiles
from repro.core.vector_index import VectorIndex
from repro.kernels import ops, ref as kref
from repro.launch.mesh import V5E, chip_peaks

D = 256


class HostRoundtripIndex:
    """The pre-engine read path, kept verbatim for comparison: the bank
    lives in host numpy, every search re-uploads it (`jnp.asarray`) and
    rebuilds the row->namespace array from a Python list, and the masked
    oracle runs eagerly (the use_kernel=False service configuration)."""

    def __init__(self, dim: int, capacity: int = 1024):
        self.dim, self.n = dim, 0
        self._bank = np.zeros((capacity, dim), np.float32)
        self._row_ns: list = []

    def add(self, vecs, ns):
        m = vecs.shape[0]
        while self.n + m > self._bank.shape[0]:
            self._bank = np.concatenate(
                [self._bank, np.zeros_like(self._bank)], axis=0)
        self._bank[self.n: self.n + m] = vecs
        self._row_ns.extend(int(x) for x in np.broadcast_to(ns, (m,)))
        self.n += m

    def search(self, queries, q_ns, k: int):
        bank = jnp.asarray(self._bank[: self.n])          # per-call upload
        row_ns = np.asarray(self._row_ns, np.int32)       # per-call rebuild
        s, i = kref.topk_mips_masked_ref(
            jnp.asarray(queries), bank, jnp.asarray(q_ns, jnp.int32),
            jnp.asarray(row_ns), k=k)
        return s, i


def _grow_and_search_loop(add_fn, search_fn, rows_per_iter: int, iters: int,
                          warmup: int = 2):
    """The serving pattern: append, then answer a query batch.  Returns
    seconds/iteration (device work fenced by block_until_ready)."""
    for _ in range(warmup):
        add_fn()
        search_fn()[1].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        add_fn()
        out = search_fn()
    out[1].block_until_ready()
    return (time.perf_counter() - t0) / iters


def run_steady(csv_rows, rows: int = 65000, batch: int = 8, iters: int = 5,
               k: int = 64, n_tenants: int = 32, json_out=None):
    print(f"\n# Retrieval steady state — device-resident engine vs "
          f"host-roundtrip path (N={rows}, B={batch}, k={k}, D={D}, CPU)")
    rng = np.random.default_rng(0)
    base = rng.standard_normal((rows, D)).astype(np.float32)
    base_ns = (np.arange(rows) % n_tenants).astype(np.int32)
    q = rng.standard_normal((batch, D)).astype(np.float32)
    q_ns = (np.arange(batch) % n_tenants).astype(np.int32)
    new_row = rng.standard_normal((1, D)).astype(np.float32)

    legacy = HostRoundtripIndex(D)
    legacy.add(base, base_ns)
    t_host = _grow_and_search_loop(
        lambda: legacy.add(new_row, [0]),
        lambda: legacy.search(q, q_ns, k), 1, iters)

    vi = VectorIndex(dim=D, use_kernel=False)
    vi.add(base, ns=base_ns)
    cap = vi.capacity
    assert vi.n + iters + 8 <= cap, \
        f"growth window {iters + 8} would cross the {cap} capacity bucket"
    t_dev = _grow_and_search_loop(
        lambda: vi.add(new_row, ns=[0]),
        lambda: vi.search_batch(q, q_ns, k=k), 1, iters)

    # zero-recompile assertion across further growth within the bucket
    with count_compiles() as cc:
        for _ in range(4):
            vi.add(new_row, ns=[0])
            _, i = vi.search_batch(q, q_ns, k=k)
        i.block_until_ready()
    if cc.count:
        raise AssertionError(
            f"device-resident search recompiled {cc.count}x while the bank "
            f"grew inside the {cap}-row capacity bucket: {cc.msgs[:3]}")

    speedup = t_host / t_dev
    print(f"rows {rows:7d} (capacity {cap}): host-roundtrip "
          f"{t_host*1e3:8.1f}ms/iter | device-resident {t_dev*1e3:8.1f}ms/iter"
          f" | speedup {speedup:5.2f}x | recompiles during growth: 0")
    csv_rows.append((f"retrieval/steady_N{rows}", t_dev * 1e6,
                     f"{speedup:.2f}x vs host-roundtrip"))
    if json_out is not None:
        json_out.append({
            "rows": rows, "capacity": cap, "batch": batch, "k": k,
            "t_host_roundtrip_ms": t_host * 1e3,
            "t_device_resident_ms": t_dev * 1e3,
            "speedup": speedup,
            "grow_steps_checked": 4, "recompiles": cc.count,
        })
    return csv_rows


def run_quantized(csv_rows, rows: int = 65000, batch: int = 8,
                  iters: int = 5, k: int = 10, n_tenants: int = 32,
                  assert_recall=None, json_out=None):
    """f32 vs int8 residency on the same steady-state serving pattern.

    `bank_bytes_read` is the per-search device traffic over the bank scan
    (the whole capacity-padded bank is streamed once per launch — the
    kernel is bandwidth-bound at serving batch sizes) plus, for the
    quantized path, the candidate-gather bytes of the exact rescore.
    Wall-clock on CPU is indicative; the bytes ratio is the claim."""
    print(f"\n# Quantized bank — f32 vs int8 + exact rescore "
          f"(N={rows}, B={batch}, k={k}, D={D}, CPU)")
    rng = np.random.default_rng(7)
    base = rng.standard_normal((rows, D)).astype(np.float32)
    base_ns = (np.arange(rows) % n_tenants).astype(np.int32)
    q = rng.standard_normal((batch, D)).astype(np.float32)
    q_ns = (np.arange(batch) % n_tenants).astype(np.int32)
    new_row = rng.standard_normal((1, D)).astype(np.float32)

    vi_f = VectorIndex(dim=D, use_kernel=False)
    vi_f.add(base, ns=base_ns)
    t_f32 = _grow_and_search_loop(
        lambda: vi_f.add(new_row, ns=[0]),
        lambda: vi_f.search_batch(q, q_ns, k=k), 1, iters)

    vi_q = VectorIndex(dim=D, use_kernel=False, quantize="int8", rescore=4)
    vi_q.add(base, ns=base_ns)
    t_int8 = _grow_and_search_loop(
        lambda: vi_q.add(new_row, ns=[0]),
        lambda: vi_q.search_batch(q, q_ns, k=k), 1, iters)

    # recall@k of the quantized index vs the exact f32 oracle (host mirror)
    s_q, i_q = vi_q.search_batch(q, q_ns, k=k)
    i_q = np.asarray(i_q)
    scores = q @ vi_q.bank[: vi_q.n].T
    mask = vi_q.alive() & (vi_q.row_namespaces()[None, :] == q_ns[:, None])
    scores = np.where(mask, scores, -np.inf)
    i_true = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    recall = float(np.mean([
        len(set(i_q[r][i_q[r] >= 0]) & set(i_true[r])) / k
        for r in range(batch)]))
    hit_rate = (vi_q.counters["rescore_hits"]
                / max(1, vi_q.counters["rescore_rows"]))

    cap = vi_q.capacity
    kc = min(cap, 1 << (int(np.ceil(np.log2(max(1, k * vi_q.rescore))))))
    bytes_f32 = cap * D * 4
    bytes_int8 = cap * D * 1 + cap * 4 + batch * kc * D * 4  # codes+scales+gather
    ratio = bytes_f32 / bytes_int8
    print(f"rows {rows:7d} (capacity {cap}): f32 {t_f32*1e3:8.1f}ms/iter | "
          f"int8+rescore {t_int8*1e3:8.1f}ms/iter")
    print(f"bank bytes read/search: f32 {bytes_f32/2**20:7.1f}MiB | "
          f"int8 {bytes_int8/2**20:7.1f}MiB | ratio {ratio:5.2f}x")
    print(f"recall@{k} vs f32 oracle: {recall:.3f} | "
          f"rescore hit rate: {hit_rate:.3f}")
    if ratio < 2.0:
        raise AssertionError(
            f"quantized bank reads only {ratio:.2f}x fewer bytes (< 2x)")
    if assert_recall is not None and recall < assert_recall:
        raise AssertionError(
            f"quantized recall@{k} {recall:.3f} < required {assert_recall}")
    csv_rows.append((f"retrieval/quantized_N{rows}", t_int8 * 1e6,
                     f"{ratio:.2f}x fewer bank bytes, recall {recall:.3f}"))
    if json_out is not None:
        json_out.append({
            "rows": rows, "capacity": cap, "batch": batch, "k": k,
            "rescore": vi_q.rescore, "candidates_per_query": kc,
            "t_f32_ms": t_f32 * 1e3, "t_int8_ms": t_int8 * 1e3,
            "bank_bytes_read_f32": bytes_f32,
            "bank_bytes_read_int8": bytes_int8,
            "bytes_ratio": ratio,
            "recall_at_k": recall, "recall_required": assert_recall,
            "rescore_hit_rate": hit_rate,
        })
    return csv_rows


def run_quick(csv_rows):
    print("\n# Retrieval microbench — fused topk_mips vs jnp oracle")
    key = jax.random.PRNGKey(0)
    K = 32
    for N in (1024, 8192, 32768):
        q = jax.random.normal(key, (64, D))
        bank = jax.random.normal(jax.random.fold_in(key, 1), (N, D))
        t_ref = _time(lambda a, b: kref.topk_mips_ref(a, b, k=K), q, bank)
        flops = 2 * 64 * N * D
        bytes_ = (64 * D + N * D) * 4
        # v5e roofline for this op (exact MIPS is bandwidth-bound at Q=64)
        peaks = chip_peaks(V5E)
        t_compute = flops / peaks.flops_bf16
        t_mem = bytes_ / peaks.hbm_bw
        print(f"N={N:6d}: jnp_ref {t_ref*1e6:9.0f}us/call | v5e roofline "
              f"compute {t_compute*1e6:6.2f}us, memory {t_mem*1e6:6.2f}us "
              f"(bound: {'memory' if t_mem > t_compute else 'compute'})")
        csv_rows.append((f"retrieval/topk_N{N}", t_ref * 1e6,
                         f"{t_mem*1e6:.2f}"))
    return csv_rows


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    out[0].block_until_ready()
    return (time.time() - t0) / iters


def run(csv_rows, steady: bool = False, quantized: bool = False,
        rows: int = 65000, batch: int = 8, iters: int = 5, k: int = 10,
        assert_recall=None, json_path=None):
    report = {"steady_state": [], "quantized": []}
    if steady:
        run_steady(csv_rows, rows=rows, batch=batch, iters=iters,
                   json_out=report["steady_state"])
    if quantized:
        run_quantized(csv_rows, rows=rows, batch=batch, iters=iters, k=k,
                      assert_recall=assert_recall,
                      json_out=report["quantized"])
    if not steady and not quantized:
        run_quick(csv_rows)
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
    ap.add_argument("--steady", action="store_true",
                    help="steady-state device-resident vs host-roundtrip "
                         "comparison + zero-recompile assertion")
    ap.add_argument("--quantized", action="store_true",
                    help="f32 vs int8 residency: latency, bank-bytes-read "
                         "ratio (asserted >= 2x) and recall@k vs the oracle")
    ap.add_argument("--rows", type=int, default=65000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--k", type=int, default=10,
                    help="top-k for the quantized recall measurement")
    ap.add_argument("--assert-recall", type=float, default=None,
                    metavar="R", help="fail if quantized recall@k < R")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_retrieval.json artifact")
    args = ap.parse_args()
    run([], steady=args.steady, quantized=args.quantized, rows=args.rows,
        batch=args.batch, iters=args.iters, k=args.k,
        assert_recall=args.assert_recall, json_path=args.json)
