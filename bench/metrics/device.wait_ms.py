"""Host waits on the device: the window's summed `device.wait` spans (the
tick thread blocked on a device result) over its `scheduler.tick` spans,
each counted once (ms per tick)."""


def read(obs):
    ticks = sum(1 for s in obs.spans if s["name"] == "scheduler.tick")
    waits = [s["dur_s"] for s in obs.spans if s["name"] == "device.wait"]
    return 1e3 * sum(waits) / ticks if ticks and waits else None
