"""Paper Figure 2 analogue: Memori accuracy mean ± std over n=3 runs
(three disjoint seed groups) per reasoning category."""
from __future__ import annotations

import statistics
import time

from benchmarks.common import evaluate
from repro.data.locomo_synth import CATEGORIES


def run(csv_rows):
    print("\n# Figure 2 — Memori accuracy mean ± std (n=3 runs)")
    t0 = time.time()
    runs = [evaluate("memori", seeds=(3 * i, 3 * i + 1)) for i in range(3)]
    us = (time.time() - t0) * 1e6 / 3
    for c in CATEGORIES:
        vals = [100 * r.per_category[c] for r in runs]
        mean = statistics.mean(vals)
        std = statistics.stdev(vals) if len(vals) > 1 else 0.0
        print(f"{c:14s} {mean:6.2f}% ± {std:5.2f}")
    overall = [100 * r.overall for r in runs]
    print(f"{'overall':14s} {statistics.mean(overall):6.2f}% ± "
          f"{statistics.stdev(overall):5.2f}")
    csv_rows.append(("fig2/overall_mean", us, f"{statistics.mean(overall):.2f}"))
    return csv_rows


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    run([])
