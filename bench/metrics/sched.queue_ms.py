"""Scheduler queue wait: mean `queued_s` of the window's answered
retrieves, as the program's response envelope reports it (ms)."""


def read(obs):
    q = [a["queued_s"] for a in obs.answers
         if a.get("ok") and a.get("queued_s") is not None]
    return 1e3 * sum(q) / len(q) if q else None
