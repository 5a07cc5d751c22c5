"""Paper Table 3 analogue: question-category distribution of the benchmark."""
from __future__ import annotations

import collections
import time

from repro.data.locomo_synth import CATEGORIES, LOCOMO_WEIGHTS, generate_conversation


def run(csv_rows):
    print("\n# Table 3 — question category distribution")
    t0 = time.time()
    counts = collections.Counter()
    for seed in range(4):
        conv = generate_conversation(seed=seed, n_sessions=6, noise_turns=20)
        counts.update(q.category for q in conv.questions)
    us = (time.time() - t0) * 1e6 / 4
    print(f"{'category':14s} {'synthetic n':>11s} {'LoCoMo n':>9s}")
    for c in CATEGORIES:
        print(f"{c:14s} {counts[c]:11d} {LOCOMO_WEIGHTS[c]:9d}")
    csv_rows.append(("table3/categories", us, sum(counts.values())))
    return csv_rows


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    run([])
