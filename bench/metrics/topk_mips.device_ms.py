"""`topk_mips` kernel: mean device time per launch in the traced window,
from the profiler's device ops (ms)."""


def read(obs):
    t = obs.device
    if t is None or not t.kernel_calls.get("topk_mips"):
        return None
    return 1e3 * t.kernel_s["topk_mips"] / t.kernel_calls["topk_mips"]
