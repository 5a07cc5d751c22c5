"""Paper Table 2 analogue: token usage + cost per query + context footprint
(gpt-4.1-mini pricing $0.8/1M tokens, as in the paper)."""
from __future__ import annotations

import time

from benchmarks.common import evaluate

PRICE_PER_TOKEN = 0.8 / 1e6
SYSTEMS = ["memori", "rag", "full-context"]


def run(csv_rows):
    print("\n# Table 2 — token usage and cost efficiency")
    results = {}
    for name in SYSTEMS:
        t0 = time.time()
        r = evaluate(name)
        us = (time.time() - t0) * 1e6 / max(1, r.n_questions)
        results[name] = r
        csv_rows.append((f"table2/{name}", us, f"{r.mean_tokens:.0f}"))
    full = results["full-context"].mean_tokens
    print(f"{'method':14s} {'added tokens':>12s} {'cost($)':>10s} {'footprint':>9s}")
    for name, r in results.items():
        print(f"{name:14s} {r.mean_tokens:12.0f} "
              f"{r.mean_tokens * PRICE_PER_TOKEN:10.6f} "
              f"{100 * r.mean_tokens / full:8.2f}%")
    saving = full / results["memori"].mean_tokens
    print(f"memori vs full-context: {saving:.1f}x cheaper per query")
    return csv_rows


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    run([])
