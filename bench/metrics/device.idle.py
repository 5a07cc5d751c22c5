"""Device idle share: 1 - (union of device op intervals) / traced window,
in %, averaged over the chips in use."""


def read(obs):
    t = obs.device
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
