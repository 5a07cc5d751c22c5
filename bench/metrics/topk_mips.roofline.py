"""`topk_mips` kernel: the window's summed least time over its summed
device time, in %.  Each launch is one tick's `plan.dense` span; its work
is what its queries' namespaces need (bench/roofline.py).  The bound that
decides the least time is printed on standard error."""
import sys

import roofline


def read(obs):
    t = obs.device
    if t is None or not t.kernel_s.get("topk_mips"):
        return None
    tenant = {f"r{it['i']}": it["tenant"] for it in obs.items}
    calls = []
    for s in obs.spans:
        if s["name"] != "plan.dense":
            continue
        per_ns = {}
        for rid in s["requests"]:
            if rid in tenant:
                per_ns[tenant[rid]] = per_ns.get(tenant[rid], 0) + 1
        if per_ns:
            calls.append(per_ns)
    if not calls:
        return None
    c = obs.config
    least, bound = roofline.window_least_time(
        calls, obs.live_rows, c["dim"], c["quantize"], c["pool"], obs.peaks)
    print(f"[roofline] topk_mips calls={len(calls)} "
          f"launches={t.kernel_calls['topk_mips']} least_s={least!r} "
          f"device_s={t.kernel_s['topk_mips']!r} bound={bound}",
          file=sys.stderr)
    return 100.0 * least / t.kernel_s["topk_mips"]
