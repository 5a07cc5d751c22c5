"""One run of one cell, in the process that holds the chip.

Set-up: build the server exactly as `python -m repro.launch.serve` builds
it (`build_server`: agent engine, MemoryService, MemoryScheduler, HTTP
frontend on localhost, plus the configuration's `serve_args`), fill the
bank through the program's write path, and let the cell's mix
(bench/mixes/<kind>.py) warm every program its traffic uses.  Window: the
open-loop load generator (bench/loadgen.py, a child process without JAX)
sends the mix's seeded schedule over HTTP.  After the window: device
memory is read, the mix reads back what it wrote through the served API,
the server is closed, and the mix compares every answer with its plain
reference.
"""
from __future__ import annotations

import gc
import json
import shutil
import subprocess
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

import data
import reference
import devtrace as trace_mod
from spec import BENCH_DIR, ROOT, Cell, load_mix, load_peaks, load_reader
from traffic import API_KEY, RETRIEVE, TENANT, check_items

sys.path.insert(0, str(ROOT / "src"))

CACHE_DIR = BENCH_DIR / ".jax_cache"
# On the TPU an op's name is its HLO text; the Pallas kernel is the
# `tpu_custom_call` inside the dense stage's jitted search, which names it.
KERNELS = {"topk_mips": r'^%_search_device(_quant)?\.\d+ = .*'
                        r'custom_call_target="tpu_custom_call"'}
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileLog:
    """Unix times of every lowering and XLA compile the process makes."""

    def __init__(self):
        import jax.monitoring as mon
        self.events: List[tuple] = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in (_LOWER, _COMPILE):
            self.events.append((time.time(), event))

    def count(self, t0: float, t1: float, event: str) -> int:
        return sum(1 for t, e in self.events if t0 <= t <= t1 and e == event)


def build(config: dict, seed: int):
    from repro.launch import serve
    argv = ["--arch", config["arch"], "--seed", str(seed % 2 ** 31),
            "--quantize", config["quantize"],
            "--max-len", str(config["max_len"]),
            "--tick-interval", str(config["tick_interval_s"]),
            "--max-batch", str(config["max_batch"]),
            "--http-port", "0", "--http-host", "127.0.0.1",
            "--api-keys", f"{API_KEY}={TENANT}"]
    if config.get("host_demo"):
        argv.append("--host-demo")
    argv += config.get("serve_args", [])
    server = serve.build_server(serve.parse_args(argv))
    svc = server.service
    # the program has no flag for these: hold it to what the
    # configuration states, and refuse a run that departs from it
    got = {"budget": svc.budgeter.budget, "pool": svc.pool,
           "quantize": svc.vindex.quantize, "dim": svc.vindex.dim}
    for key, value in got.items():
        if value != config[key]:
            raise SystemExit(f"the served program has {key}={value!r}, the "
                             f"configuration states {config[key]!r}")
    return server


def fill(server, config: dict, seed: int, vectors: np.ndarray) -> None:
    svc = server.service
    for rec in data.bulk_records(vectors, config["rows_per_tenant"], seed):
        svc.store.apply_wal(rec)
    if svc.vindex.n != vectors.shape[0]:
        raise RuntimeError(f"the bank holds {svc.vindex.n} rows, the "
                           f"configuration states {vectors.shape[0]}")
    log(f"[fill] rows={svc.vindex.n} capacity={svc.vindex.capacity}")


def _spans(traces: List[dict], t0: float, t1: float) -> List[dict]:
    """Unique program spans that start inside the window [t0, t1] (a
    tick's spans appear in every request's tree), each with the request
    ids it served."""
    out: Dict[tuple, dict] = {}

    def walk(sp, t0, rid):
        # one span of a shared tick is opened in every request's tree a
        # few microseconds apart, with one measured duration: that
        # duration identifies it
        start = t0 + sp["start_s"]
        key = (sp["name"], sp["duration_s"])
        d = out.setdefault(key, {"name": sp["name"], "start_unix": start,
                                 "dur_s": sp["duration_s"] or 0.0,
                                 "requests": set()})
        d["start_unix"] = min(d["start_unix"], start)
        d["requests"].add(rid)
        for c in sp.get("children", ()):
            walk(c, t0, rid)

    for tr in traces:
        for c in tr["root"].get("children", ()):
            walk(c, tr["started_unix"], tr["request_id"])
    return [d for d in out.values() if t0 <= d["start_unix"] <= t1]


def setup(config: dict, traffic: dict, seed: int, mix):
    """The served stack, filled and warmed by `mix`, and the bulk vectors
    it holds."""
    t = time.time()
    server = build(config, seed)
    server.frontend.start()
    vectors = data.bulk_vectors(seed, config["tenants"]
                                * config["rows_per_tenant"], config["dim"])
    t_build = time.time()
    fill(server, config, seed, vectors)
    t_fill = time.time()
    mix.warm(server, config, traffic)
    t_warm = time.time()
    # the fill leaves the collector a debt of millions of young objects; a
    # full collection paid here keeps a multi-second pause out of the
    # window, where it fell in some runs and not in others
    gc.collect()
    log(f"[setup] build_s={t_build - t:.2f} fill_s={t_fill - t_build:.2f} "
        f"warm_s={t_warm - t_fill:.2f} gc_s={time.time() - t_warm:.2f}")
    return server, vectors


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax

    from repro.obs import telemetry

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileLog()
    config, traffic = cell.config, cell.traffic
    mix = load_mix(cell.mix)
    items = mix.schedule(traffic, config, seed, seconds)
    check_items(items)
    n_ops = sum(len(it["ops"]) for it in items)
    if trace:
        telemetry.set_telemetry(telemetry.Telemetry(
            trace_capacity=4 * n_ops + 1024))
    server, vectors = setup(config, traffic, seed, mix)

    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_mod.SYNC):
            sync_unix = time.time()
    job = {"address": server.frontend.address, "api_key": API_KEY,
           "workers": traffic["workers"], "drain_s": 60.0,
           "window_s": seconds, "items": items}
    proc = subprocess.run([sys.executable, str(BENCH_DIR / "loadgen.py")],
                          input=json.dumps(job).encode(),
                          capture_output=True, check=False)
    if proc.returncode != 0:
        raise RuntimeError("load generator failed: "
                           + proc.stderr.decode()[-2000:])
    res = json.loads(proc.stdout)
    results = res["items"]
    t0, t1 = res["t0_unix"], res["end_unix"]
    if trace:
        jax.profiler.stop_trace()
    dev = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev[:cell.chips])
    n_lower = compiles.count(t0, t1, _LOWER)
    n_compile = compiles.count(t0, t1, _COMPILE)
    traces = (telemetry.get_telemetry().recent_traces(
        limit=4 * n_ops + 1024) if trace else [])
    t_after = time.time()
    after = mix.after_window(server, config, items, results)
    log(f"[after] after_window_s={time.time() - t_after:.3f}")
    server.frontend.close()
    server.service.close()
    del server
    gc.collect()

    numbers = mix.compare(items, results, SimpleNamespace(
        config=config, seed=seed, vectors=vectors, after=after))
    limits = config["limits"]
    correct = reference.verdict(numbers, limits)

    ops = op_results(results)
    answers = [o for o in ops if o["path"] == RETRIEVE]
    lat = latencies(answers)
    late = np.array([r.get("late_s", 0.0) for r in results])
    values = {"setup_s": t0 - t_start,
              "retrieve_p50_ms": _pct(lat, 50) * 1e3,
              "retrieve_p95_ms": _pct(lat, 95) * 1e3,
              "hbm_peak_gib": peak / 2 ** 30}
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    out = {"correct": correct,
           "attempted": len(ops),
           "failed": int(sum(not o.get("ok") for o in ops))}
    breakdown = None
    if trace:
        spans = _spans(traces, t0, t1)
        events = trace_mod.events_from_xplane(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        dtr = trace_mod.reduce(events, (t0, t1), sync_unix, KERNELS,
                               spans)
        _require_kernels(cell, dtr)
        # what a reader sees: `answers` are the retrieve ops' results,
        # `ops` every op's; the window's first op of item i was request
        # r<i>, a later op j request r<i>.<j>
        obs = SimpleNamespace(
            config=config, traffic=traffic, items=items, answers=answers,
            ops=ops, spans=spans, device=dtr,
            live_rows=mix.live_rows(config, items, results),
            peaks=load_peaks(dev[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(obs)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if dtr is not None:
            device["busy_s"] = dtr.busy_s
            device["window_s"] = dtr.window_s
            breakdown = {"device_ops": [[k, v] for k, v in dtr.ops],
                         "idle_gaps": [[k, v] for k, v in dtr.gaps]}
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}

    log(f"[window] requests={len(items)} ops={len(ops)} "
        f"seconds={seconds} drained_s={t1 - t0:.3f}")
    for path in sorted({o["path"] for o in ops} - {RETRIEVE}):
        # no end-to-end metric yet: the first cell that writes sets the
        # bound of one from its spreads on the chip
        x = latencies([o for o in ops if o["path"] == path])
        name = path.rsplit("/", 1)[-1]
        log(f"[window] {name}_p50_ms={_pct(x, 50) * 1e3!r} "
            f"{name}_p95_ms={_pct(x, 95) * 1e3!r} n={x.size}")
    log(f"[window] compiles_in_window={n_compile} "
        f"lowerings_in_window={n_lower}")
    log(f"[window] generator_late_p50_ms={_pct(late, 50) * 1e3:.3f} "
        f"late_p95_ms={_pct(late, 95) * 1e3:.3f} "
        f"late_max_ms={float(late.max(initial=0.0)) * 1e3:.3f}")
    log(f"[e2e] " + " ".join(f"{k}={v!r}" for k, v in values.items()))
    for line in reference.check_lines(numbers, limits):
        log(line)
    out.update(metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    return out


def _require_kernels(cell: Cell, dtr) -> None:
    """A kernel the cell's metrics name must show in the traced window:
    where no device op matches its pattern, the pattern has gone stale
    (a renamed kernel), and a silent run would drop its metrics."""
    for kernel, pattern in KERNELS.items():
        if not any(m["name"].startswith(kernel + ".")
                   for m in cell.per_layer):
            continue
        if dtr is None or not dtr.kernel_calls.get(kernel):
            raise RuntimeError(
                f"no device op in the traced window matches {kernel}'s "
                f"pattern {pattern!r}; the heaviest ops were "
                f"{[k for k, _ in dtr.ops] if dtr else []}")


def op_results(results: List[dict]) -> List[dict]:
    """Every op's result of the load generator's items, in item order."""
    return [o for r in results for o in r["ops"]]


def latencies(ops: List[dict]) -> np.ndarray:
    """Seconds per op; a failed or unsent op is infinitely slow."""
    return np.array([o["latency_s"] if o.get("ok") else np.inf
                     for o in ops], np.float64)


def _pct(x: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (a failed request counts as infinitely
    slow)."""
    if x.size == 0:
        return float("nan")
    s = np.sort(x)
    return float(s[max(0, int(np.ceil(q / 100.0 * s.size)) - 1)])
