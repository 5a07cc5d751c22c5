"""The traffic generator's shared draws, for the mix modules'
`schedule` (bench/mixes/<kind>.py).  Numpy only, so the load-generator
process never imports JAX.

A traffic file (bench/traffic/<name>.json) holds the parameters its mix
reads; every mix reads:

    mix             the mix module's name (absent: "rank")
    rate_per_s      offered arrivals per second (fixed in the cell)
    tenant_skew     Zipf exponent over the configuration's tenants
    workers         the load generator's connections

Every seed gets the same number of arrivals, rate x seconds, spread over
the window by exponential gaps (a Poisson process conditioned on its
count), so seeds change the order and the tenants, not the amount of work.
"""
from __future__ import annotations

import string
from typing import Dict, List

import numpy as np

API_KEY = "bench-key"
TENANT = "bench"
RETRIEVE = "/v1/retrieve"


def bulk_namespace(tenant: int) -> str:
    """Client-side namespace of bulk tenant `tenant` (the frontend scopes
    it under the api key's tenant: `bench/bulk<i>`)."""
    return f"bulk{tenant}"


def zipf_tenants(rng: np.random.Generator, n_tenants: int, skew: float,
                 size: int) -> np.ndarray:
    """`size` tenant ids, Zipf(`skew`) over ranks, ranks mapped to tenants
    by a seeded permutation (which tenants are hot changes with the seed,
    the shape of the skew does not)."""
    ranks = np.arange(1, n_tenants + 1, dtype=np.float64)
    p = ranks ** -float(skew)
    p /= p.sum()
    perm = rng.permutation(n_tenants)
    return perm[rng.choice(n_tenants, size=size, p=p)]


def fill_template(template: str, vocab: Dict[str, List[str]],
                  rng: np.random.Generator) -> str:
    fields = [f for _, f, _, _ in string.Formatter().parse(template) if f]
    return template.format(**{f: vocab[f][rng.integers(len(vocab[f]))]
                              for f in fields})


def arrival_times(rng: np.random.Generator, n: int,
                  seconds: float) -> np.ndarray:
    gaps = rng.exponential(size=n + 1)
    return np.cumsum(gaps[:n]) / gaps.sum() * float(seconds)


def check_items(items: List[dict]) -> None:
    """Refuse a schedule with a retrieve after an item's first op.  The
    load generator gives item i's first op the request id r<i>, a later
    op j r<i>.<j>; the metric readers map r<i> to the item's tenant, so a
    later retrieve would drop out of their readings unseen."""
    for it in items:
        if any(op["path"] == RETRIEVE for op in it["ops"][1:]):
            raise ValueError(f"item {it['i']} has a {RETRIEVE} op after "
                             f"its first; a mix's retrieve comes first")
