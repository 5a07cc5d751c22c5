"""The control: the reference put in the program's place, computed one
precision step below what the configuration states, must come out not
correct.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed it makes the cell's data and the requests of a window at the
cell's rate, answers them with the control, and prints the numbers the
comparison reads.  Controls, by the bank's residency:

* f32 at `Precision.HIGHEST` -> scores at `Precision.HIGH` (three bf16
  passes, written out as bf16 products with float32 sums), on the chip;
* int8 codes with exact f32 rescore -> int4 codes for the candidate scan,
  rescore at `Precision.HIGH`; and int8 codes with no rescore at all.

`numpy` emulations of the same arithmetic serve the CPU tests.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Callable, Dict, List, Sequence

import numpy as np

import data
import reference
import spec
from traffic import schedule

RESCORE = 4                       # the served over-fetch of the int8 path


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), kept as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.view(np.float32)


def dot_high_np(rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    """rows @ q as a TPU computes f32 at `Precision.HIGH`: each operand
    split into a bf16 high and low part, three bf16 products summed in
    float32."""
    rh, qh = bf16(rows), bf16(q)
    rl, ql = bf16(rows - rh), bf16(q - qh)
    return (rh @ qh + rh @ ql + rl @ qh).astype(np.float32)


def quantize(rows: np.ndarray, bits: int):
    """Symmetric per-row codes and scales."""
    m = (1 << (bits - 1)) - 1
    scale = np.abs(rows).max(axis=1) / m
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    codes = np.clip(np.rint(rows / scale[:, None]), -m, m)
    return codes.astype(np.float32), scale


def _topk(s: np.ndarray, k: int) -> np.ndarray:
    return np.lexsort((np.arange(s.size), -s.astype(np.float64)))[:k]


def ranker(mode: str, k: int, dot: Callable) -> Callable:
    """(rows f32, q f32) -> local row ids of the control's top k."""
    def high(rows, q):
        return _topk(dot(rows, q), k)

    def int4_high(rows, q):
        codes, scale = quantize(rows, 4)
        cand = _topk((codes @ q) * scale, min(rows.shape[0], RESCORE * k))
        return cand[_topk(dot(rows[cand], q), k)]

    def int8_norescore(rows, q):
        codes, scale = quantize(rows, 8)
        return _topk((codes @ q) * scale, k)

    return {"high": high, "int4_high": int4_high,
            "int8_norescore": int8_norescore}[mode]


def control_answers(items: Sequence[dict], vectors: np.ndarray,
                    rows_per_tenant: int, k: int, rank: Callable,
                    embed_f32: Callable) -> List[dict]:
    out = []
    for it in items:
        row0 = it["tenant"] * rows_per_tenant
        rows = vectors[row0: row0 + rows_per_tenant]
        ids = row0 + rank(rows, embed_f32(it["body"]["query"]))
        w = float(it["body"].get("dense_weight", 1.0))
        out.append({"ok": True, "row_ids": ids.tolist(),
                    "scores": reference.rrf_scores(ids.size, w).tolist()})
    return out


def embed_f32(dim: int) -> Callable:
    e = reference.Embedder(dim)
    return lambda text: e(text).astype(np.float32)


def readings(cell, seed: int, seconds: float, mode: str,
             dot: Callable) -> Dict[str, float]:
    c = cell.config
    vectors = data.bulk_vectors(seed, c["tenants"] * c["rows_per_tenant"],
                                c["dim"])
    items = schedule(cell.traffic, c, seed, seconds)
    ans = control_answers(items, vectors, c["rows_per_tenant"], c["pool"],
                          ranker(mode, c["pool"], dot), embed_f32(c["dim"]))
    return reference.compare(items, ans, vectors, c["rows_per_tenant"],
                             c["pool"], reference.Embedder(c["dim"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3

    @jax.jit
    def _dot_high(parts):
        rh, rl, qh, ql = parts

        def mul(a, b):
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

        return mul(rh, qh) + mul(rh, ql) + mul(rl, qh)

    def dot_high(rows, q):
        # the three bf16 passes of `Precision.HIGH`, with the operands split
        # into bf16 high and low parts on the host: a split inside the
        # program is elided by XLA's excess-precision rewrite, and the
        # precision flag alone is ignored for a matrix-vector product (on a
        # TPU v5e both read at float32 level)
        parts = []
        for x in (rows, q):
            hi = bf16(x)
            lo = bf16(x - hi)
            parts += [hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)]
        return np.asarray(_dot_high(tuple(jnp.asarray(p) for p in parts)))

    modes = ["high"] if cell.config["quantize"] == "none" else \
        ["int4_high", "int8_norescore"]
    for seed in (int(s) for s in args.seeds.split(",")):
        for mode in modes:
            r = readings(cell, seed, args.seconds, mode, dot_high)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": mode, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
