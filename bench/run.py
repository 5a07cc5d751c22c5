"""Memori benchmark: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Builds the served memory layer on the chip, fills it from the seed, offers
the cell's open-loop traffic over HTTP for `--seconds`, checks every answer
against the plain reference, and prints one JSON line as the last line of
stdout: `correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics with --trace 0, its per-layer metrics with --trace 1), `device`
and, traced, `breakdown`; `checks` (each compared number beside its
limit) comes last.  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import spec  # noqa: E402


def require_chips(n: int) -> str:
    """The device kind, or exit: a run measures a TPU or nothing."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < n:
        print(f"bench: the cell needs {n} chips, JAX found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[0].device_kind


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    kind = require_chips(cell.chips)
    spec.load_peaks(kind)               # an unknown device is an error
    import cell as cell_mod
    out = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
