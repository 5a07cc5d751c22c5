"""Dense stage: mean duration of the window's `plan.dense` spans, one per
tick's retrieve batch (ms)."""


def read(obs):
    d = [s["dur_s"] for s in obs.spans if s["name"] == "plan.dense"]
    return 1e3 * sum(d) / len(d) if d else None
