"""The configuration's bulk data, made from the seed: `tenants` tenants of
`rows_per_tenant` rows each, every row a seeded unit vector with a seeded
(subject, predicate, object) triple, handed to the program as WAL flush
records (the recovery path, which lands in the program's one write path).

Bulk tenant t owns bank rows [t * rows_per_tenant, (t + 1) * rows_per_tenant)
when the bulk is the first thing written to an empty store.  The reference
reads the same `vectors` array; it never reads the program's bank.
Numpy only.
"""
from __future__ import annotations

from typing import Iterator, Sequence

import numpy as np

from traffic import TENANT, bulk_namespace

NAMES = ("Caroline", "Melanie", "Gordon", "Adam", "Luiz", "Joanna", "Nate",
         "Audrey", "Marcus", "Priya", "Tomas", "Elena")
PREDICATES = ("likes", "works as", "lives in", "visited", "owns",
              "learned", "plays", "cooked", "bought", "met")
OBJECTS = ("sushi", "lasagna", "ramen", "tacos", "paella", "chess",
           "pottery", "archery", "kayaking", "origami", "fencing", "Lisbon",
           "Osaka", "Tallinn", "Galway", "Cusco", "puppy", "kitten", "parrot",
           "telescope", "drone", "typewriter", "kayak", "Iceland", "Kyoto",
           "Sicily", "the cello", "woodworking", "calligraphy")


def bulk_vectors(seed: int, rows: int, dim: int) -> np.ndarray:
    """(rows, dim) float32 unit rows from the seed."""
    rng = np.random.default_rng([seed, 1])
    v = rng.standard_normal((rows, dim), dtype=np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v


def bulk_records(vectors: np.ndarray, rows_per_tenant: int, seed: int,
                 chunk: int = 8192, session_rows: int = 32
                 ) -> Iterator[dict]:
    """WAL flush records holding `vectors` in tenant order, each tenant's
    rows in sessions of `session_rows`."""
    rng = np.random.default_rng([seed, 2])
    rows, dim = vectors.shape
    for start in range(0, rows, chunk):
        stop = min(rows, start + chunk)
        subj = rng.integers(0, 2, stop - start)
        pred = rng.integers(0, len(PREDICATES), stop - start)
        obj = rng.integers(0, 64, stop - start)
        sessions, r = [], start
        while r < stop:
            ten, off = divmod(r, rows_per_tenant)
            m = min(session_rows - off % session_rows, stop - r,
                    rows_per_tenant - off)
            ns = f"{TENANT}/{bulk_namespace(ten)}"
            sid = f"s{off // session_rows}"
            ts = 1.67e9 + 60.0 * off
            trs = []
            for j in range(r - start, r - start + m):
                o = int(obj[j])
                trs.append({
                    "subject": NAMES[(ten + int(subj[j])) % len(NAMES)],
                    "predicate": PREDICATES[int(pred[j])],
                    "object": f"{OBJECTS[(ten + o) % len(OBJECTS)]} {o}",
                    "conversation_id": ns, "session_id": sid,
                    "timestamp": ts + j, "source_text": "",
                    "confidence": 1.0})
            sessions.append({"namespace": ns,
                             "summary": {"conversation_id": ns,
                                         "session_id": sid, "timestamp": ts,
                                         "text": f"bulk session {sid}"},
                             "triples": trs})
            r += m
        yield {"op": "flush", "sessions": sessions, "n_rows": stop - start,
               "dim": dim,
               "vecs": np.ascontiguousarray(vectors[start:stop],
                                            "<f4").tobytes()}


def tenant_rows(tenant: int, rows_per_tenant: int) -> Sequence[int]:
    return range(tenant * rows_per_tenant, (tenant + 1) * rows_per_tenant)
