"""One sweep of offered rates on the chip, to find a cell's knee: the
highest rate at which completions keep pace with arrivals over the window
with no growing backlog.  Not part of a benchmark run: the rate it finds is
written into the cell's traffic file by hand.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds 5 \
        --rates 100,200,400,800

Prints per rate: requests, p50/p95 latency, the median latency of the
window's first and last fifths (a backlog that grows shows as the last
fifth lagging the first), and the time the queue took to drain after the
last arrival.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import cell as cell_mod  # noqa: E402
import spec  # noqa: E402
from run import require_chips  # noqa: E402
from traffic import API_KEY, schedule  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    require_chips(cell.chips)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cell_mod.CACHE_DIR))
    compiles = cell_mod.CompileLog()
    config, traffic = cell.config, cell.traffic
    server, _ = cell_mod.setup(config, traffic, args.seed)
    print(f"[knee] workload={args.workload} setup_s={time.time() - T_START:.1f}",
          flush=True)
    rows = []
    for j, rate in enumerate(float(r) for r in args.rates.split(",")):
        items = schedule(dict(traffic, rate_per_s=rate), config,
                         args.seed + j + 1, args.seconds)
        job = {"address": server.frontend.address, "api_key": API_KEY,
               "workers": traffic["workers"], "drain_s": 60.0,
               "items": items}
        proc = subprocess.run(
            [sys.executable, str(spec.BENCH_DIR / "loadgen.py")],
            input=json.dumps(job).encode(), capture_output=True, check=True)
        res = json.loads(proc.stdout)
        ans = res["retrieves"]
        lat = np.array([a["latency_s"] if a.get("ok") else np.inf
                        for a in ans])
        fifth = max(1, len(lat) // 5)
        row = {"rate": rate, "requests": len(ans),
               "failed": int(sum(not a.get("ok") for a in ans)),
               "p50_ms": cell_mod._pct(lat, 50) * 1e3,
               "p95_ms": cell_mod._pct(lat, 95) * 1e3,
               "first_fifth_p50_ms": float(np.median(lat[:fifth])) * 1e3,
               "last_fifth_p50_ms": float(np.median(lat[-fifth:])) * 1e3,
               "drain_s": res["end_unix"] - res["t0_unix"] - args.seconds,
               "late_max_ms": max(a.get("late_s", 0.0) for a in ans) * 1e3,
               "mean_batch": float(np.mean([a.get("batch_size") or 0
                                            for a in ans])),
               "compiles": compiles.count(res["t0_unix"], res["end_unix"],
                                          cell_mod._COMPILE)}
        rows.append(row)
        print("[knee] " + json.dumps(row), flush=True)
    server.frontend.close()
    server.service.close()
    print(json.dumps({"knee_sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
