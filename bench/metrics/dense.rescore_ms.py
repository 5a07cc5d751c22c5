"""int8 rescore, host side: mean duration of the window's `dense.rescore`
spans (mirror gather, upload, rescore dispatch, counters) less the
`device.wait` spans that start inside each (ms)."""


def read(obs):
    waits = [s for s in obs.spans if s["name"] == "device.wait"]
    own = []
    for s in obs.spans:
        if s["name"] != "dense.rescore":
            continue
        end = s["start_unix"] + s["dur_s"]
        own.append(s["dur_s"] - sum(w["dur_s"] for w in waits
                                    if s["start_unix"] <= w["start_unix"]
                                    <= end))
    return 1e3 * sum(own) / len(own) if own else None
