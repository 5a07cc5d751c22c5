"""Operations and bytes the dense stage's `topk_mips` calls need, and their
least time on the chip.

The count is of the work a call's semantics need, whatever implements it:
each live row of each distinct namespace the call queries, read once, plus
the queries and the top-k outputs; 2 * dim operations per row per query of
that namespace.  Capacity, padding and the kernel's block size are what
today's kernel happens to stream and never enter the count, so a kernel
that reads only the queried tenants' rows still scores at most 100%.
"""
from __future__ import annotations

from typing import Dict, Iterable, Mapping, Tuple

ELEM_BYTES = {"none": 4, "int8": 1}     # bank element, by residency
SCALE_BYTES = {"none": 0, "int8": 4}    # per-row dequant scale


def call_cost(queries_per_ns: Mapping[int, int],
              live_rows: Mapping[int, int], dim: int, quantize: str,
              k: int) -> Tuple[float, float]:
    """(operations, bytes) of one call: `queries_per_ns` counts the call's
    queries by namespace, `live_rows` gives each namespace's live rows."""
    rows = sum(live_rows[ns] for ns in queries_per_ns)
    q = sum(queries_per_ns.values())
    flops = sum(2.0 * dim * live_rows[ns] * n
                for ns, n in queries_per_ns.items())
    bank = rows * (dim * ELEM_BYTES[quantize] + SCALE_BYTES[quantize])
    io = q * dim * 4 + q * k * 8        # f32 queries in; f32 score + i32 id
    return flops, float(bank + io)


def least_time(flops: float, nbytes: float, quantize: str,
               peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least seconds the chip could take, and which bound sets it.  The
    operation peak is the chip's fastest for the bank's element type (bf16
    for float rows, int8 for int8 codes), so the bound stays a floor."""
    rate = peaks["int8_op_per_s"] if quantize == "int8" \
        else peaks["bf16_flop_per_s"]
    t_ops = flops / rate
    t_mem = nbytes / peaks["hbm_byte_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def window_least_time(calls: Iterable[Mapping[int, int]],
                      live_rows: Mapping[int, int], dim: int, quantize: str,
                      k: int, peaks: Dict[str, float]) -> Tuple[float, str]:
    """Summed least time of the window's calls, and the bound that set
    most of it."""
    total = {"memory": 0.0, "compute": 0.0}
    for call in calls:
        t, bound = least_time(*call_cost(call, live_rows, dim, quantize, k),
                              quantize, peaks)
        total[bound] += t
    return total["memory"] + total["compute"], max(total, key=total.get)
