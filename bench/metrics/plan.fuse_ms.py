"""Fuse stage: mean duration of the window's `plan.fuse` spans, one per
tick's retrieve batch: the host's dispatch of the RRF fusion, the wait for
its result being `device.wait` (ms)."""


def read(obs):
    d = [s["dur_s"] for s in obs.spans if s["name"] == "plan.fuse"]
    return 1e3 * sum(d) / len(d) if d else None
