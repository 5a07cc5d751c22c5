"""The one traffic generator: a seeded open-loop schedule from a mix's
parameters (bench/traffic/<name>.json).  Numpy only, so the load-generator
process never imports JAX.

A mix file holds:

    rate_per_s      offered arrivals per second (fixed in the cell)
    tenant_skew     Zipf exponent over the configuration's tenants
    retrieve        the JSON body fields every retrieve carries besides
                    namespace and query ("stages", "top_k", ...)
    query           {"templates": [...], "vocab": {field: [words]}}

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


def _fill(template: str, vocab: Dict[str, List[str]],
          rng: np.random.Generator) -> str:
    fields = [f for _, f, _, _ in string.Formatter().parse(template) if f]
    return template.format(**{f: vocab[f][rng.integers(len(vocab[f]))]
                              for f in fields})


def arrival_times(rng: np.random.Generator, n: int,
                  seconds: float) -> np.ndarray:
    gaps = rng.exponential(size=n + 1)
    return np.cumsum(gaps[:n]) / gaps.sum() * float(seconds)


def schedule(traffic: dict, config: dict, seed: int,
             seconds: float) -> List[dict]:
    """The run's requests in due order: dicts with `due` (seconds from the
    window's start), `tenant` and `body` (the /v1/retrieve JSON)."""
    rng = np.random.default_rng(seed)
    n = int(round(float(traffic["rate_per_s"]) * float(seconds)))
    due = arrival_times(rng, n, seconds)
    tenants = zipf_tenants(rng, int(config["tenants"]),
                           float(traffic["tenant_skew"]), n)
    q = traffic["query"]
    out = []
    for i in range(n):
        ns = bulk_namespace(int(tenants[i]))
        body = {"namespace": ns,
                "query": _fill(q["templates"][rng.integers(
                    len(q["templates"]))], q["vocab"], rng),
                **traffic["retrieve"]}
        out.append({"i": i, "due": float(due[i]),
                    "tenant": int(tenants[i]), "body": body})
    return out
