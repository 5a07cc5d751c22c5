"""Scheduler tick: mean duration of the window's `scheduler.tick` spans,
each tick counted once (ms)."""


def read(obs):
    d = [s["dur_s"] for s in obs.spans if s["name"] == "scheduler.tick"]
    return 1e3 * sum(d) / len(d) if d else None
