"""The `rank` mix: read-only raw retrieval.  Each item is one /v1/retrieve
of a Zipf-drawn bulk tenant, with the traffic file's `retrieve` fields
("stages", "top_k", ...) and a query filled from its `query` templates:

    query           {"templates": [...], "vocab": {field: [words]}}

The reference is dense MIPS over the query's namespace and the RRF of that
single ranking (bench/reference.py); nothing is written, so nothing is
read back.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import data
import reference
from traffic import (RETRIEVE, TENANT, arrival_times, bulk_namespace,
                     fill_template, zipf_tenants)

KEEP = ["row_ids", "scores"]


def schedule(traffic: dict, config: dict, seed: int,
             seconds: float) -> List[dict]:
    """The run's items in due order: `i`, `due` (seconds from the window's
    start), `tenant` and `ops`, one /v1/retrieve."""
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
                "query": fill_template(q["templates"][rng.integers(
                    len(q["templates"]))], q["vocab"], rng),
                **traffic["retrieve"]}
        out.append({"i": i, "due": float(due[i]),
                    "tenant": int(tenants[i]),
                    "ops": [{"path": RETRIEVE, "body": body, "keep": KEEP}]})
    return out


def warm(server, config: dict, traffic: dict) -> None:
    """Every batch bucket the scheduler can form, for the mix's stages."""
    from repro.core.api import RetrieveRequest
    svc = server.service
    body = traffic["retrieve"]
    b = 1
    while b <= config["max_batch"]:
        for n in sorted({b, b // 2 + 1}):
            svc.execute([RetrieveRequest(
                namespace=f"{TENANT}/bulk{i % config['tenants']}",
                query=f"What does {data.NAMES[i % len(data.NAMES)]} like?",
                top_k=body.get("top_k"),
                stages=tuple(body["stages"]) if "stages" in body else None)
                for i in range(n)])
        b *= 2


def after_window(server, config: dict, items: Sequence[dict],
                 results: Sequence[dict]) -> dict:
    return {}


def live_rows(config: dict, items: Sequence[dict],
              results: Sequence[dict]) -> Dict[int, int]:
    return {t: config["rows_per_tenant"] for t in range(config["tenants"])}


def compare(items: Sequence[dict], results: Sequence[dict],
            ctx) -> Dict[str, float]:
    """Every item's retrieve (its first op) against the reference.

    missing        requests with no answer, or an error for one
    answer_errors  answers of the wrong length, with a duplicate id or an
                   id outside the query's namespace, or with a fused score
                   off the reference's score for its rank by more than two
                   float32 ulps (the device's division may round apart
                   from numpy's by one)
    dense_gap      the widest gap by which a served rank's exact score
                   lies below the exact score of the reference's row at
                   that rank, over |q| |x|: 0 for an exact ranking, above
                   0 only where the ranking swapped rows
    """
    c, vectors = ctx.config, ctx.vectors
    rows_per_tenant, k = c["rows_per_tenant"], c["pool"]
    embed = reference.Embedder(c["dim"])
    missing = answer_errors = 0
    dense_gap = 0.0
    for it, res in zip(items, results):
        ans = res["ops"][0]
        if not ans.get("ok") or "row_ids" not in ans:
            missing += 1
            continue
        body = it["ops"][0]["body"]
        t = it["tenant"]
        row0 = t * rows_per_tenant
        rows = vectors[row0: row0 + rows_per_tenant]
        q = embed(body["query"])
        ref, s = reference.exact_topk(rows, row0, q, k)
        got = np.asarray(ans["row_ids"], np.int64)
        local = got - row0
        w = float(body.get("dense_weight", 1.0))
        want = reference.rrf_scores(ref.size, w).astype(np.float64)
        scores = np.asarray(ans["scores"], np.float64)
        if (got.size != ref.size or scores.size != got.size
                or len(set(got.tolist())) != got.size
                or np.any(local < 0) or np.any(local >= rows_per_tenant)
                or np.any(np.abs(scores - want) > 2 ** -22 * want)):
            answer_errors += 1
            continue
        norms = np.linalg.norm(rows.astype(np.float64), axis=1)
        ref_local = ref - row0
        gap = (s[ref_local] - s[local]) / (
            np.linalg.norm(q) * np.maximum(norms[ref_local], norms[local]))
        dense_gap = max(dense_gap, float(gap.max()))
    return {"missing": missing, "answer_errors": answer_errors,
            "dense_gap": dense_gap}
