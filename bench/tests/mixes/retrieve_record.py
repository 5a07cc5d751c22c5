"""A writing mix that only the tests run: each item is the `rank` mix's
retrieve, then a two-message /v1/record of the turn once the reply is
back, into a namespace of its own.  After the window every acknowledged
record is read back through the served API: a budgeted retrieve of the
turn's namespace must return the fact the turn stated.

It lives with the tests, outside bench/mixes/, to show that a mix that
writes plugs into the harness with no edit of a file under bench/.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import loadgen
import spec
from traffic import API_KEY, RETRIEVE

RANK = spec.load_mix("rank")
RECORD = "/v1/record"
SAID = "I really like "


def _fact(item: dict) -> str:
    """The object of the turn's "likes" fact, from its first message, as
    the extractor stores it (lower case)."""
    text = item["ops"][1]["body"]["messages"][0]["text"]
    return text[len(SAID): -1].lower()


def schedule(traffic: dict, config: dict, seed: int,
             seconds: float) -> List[dict]:
    items = RANK.schedule(traffic, config, seed, seconds)
    rng = np.random.default_rng([seed, 3])
    objects = traffic["query"]["vocab"]["object"]
    for it in items:
        said = f"{SAID}{objects[rng.integers(len(objects))]}."
        it["ops"].append({"path": RECORD, "keep": [], "body": {
            "namespace": f"turn{it['i']}", "session_id": "s0",
            "messages": [{"speaker": "user", "text": said},
                         {"speaker": "assistant", "text": "Noted."}]}})
    return items


def warm(server, config: dict, traffic: dict) -> None:
    RANK.warm(server, config, traffic)


def after_window(server, config: dict, items: Sequence[dict],
                 results: Sequence[dict]) -> Dict[int, List[str]]:
    """Item i -> the objects a read-back of its turn's namespace returns,
    for every item whose record was acknowledged."""
    acked = [it for it, r in zip(items, results) if r["ops"][1]["ok"]]
    reads = [{"i": k, "due": 0.0, "tenant": it["tenant"], "ops": [{
        "path": RETRIEVE, "keep": ["triples"],
        "body": {"namespace": it["ops"][1]["body"]["namespace"],
                 "query": it["ops"][1]["body"]["messages"][0]["text"],
                 "stages": ["dense", "budget"]}}]}
        for k, it in enumerate(acked)]
    got = loadgen.run({"address": server.frontend.address,
                       "api_key": API_KEY, "workers": 4, "drain_s": 60.0,
                       "items": reads})["items"]
    return {it["i"]: [t["object"] for t in r["ops"][0].get("triples", ())]
            for it, r in zip(acked, got)}


def live_rows(config: dict, items: Sequence[dict],
              results: Sequence[dict]) -> Dict[int, int]:
    return RANK.live_rows(config, items, results)


def compare(items: Sequence[dict], results: Sequence[dict],
            ctx) -> Dict[str, float]:
    """The retrieves as `rank` judges them, and

    unacked   records that were not acknowledged
    unread    acknowledged records whose fact the read-back does not show
    """
    numbers = RANK.compare(items, results, ctx)
    read = ctx.after
    numbers["unacked"] = sum(not r["ops"][1]["ok"] for r in results)
    numbers["unread"] = sum(_fact(it) not in read[it["i"]]
                            for it in items if it["i"] in read)
    return numbers
