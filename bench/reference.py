"""The plain reference and the comparison that decides `correct`.

Imports nothing of the program.  The reference holds its own copy of the
data (the seeded bulk vectors of bench/data.py) and of the query
embedding's semantics (a hashed bag-of-words: per-word Gaussian vectors
from the word's FNV-1a hash, synonyms folded, mean, L2-normalised), and
computes in float64:

* dense: exact maximum-inner-product top-k over the query's namespace,
  ties to the lower row id;
* fuse: weighted reciprocal-rank fusion of that ranking, score
  w / (60 + rank + 1) in float32 as the served API defines it.

`compare` reads every answer of the window and returns the numbers that
are held to the configuration's limits.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence

import numpy as np

RRF_C = 60.0

# the embedding's synonym lexicon (API semantics of the hashed embedder)
SYNONYMS = {
    "job": ["work", "works", "working", "profession", "living", "occupation",
            "career", "trade", "employed"],
    "food": ["dish", "meal", "cuisine", "eat", "eats", "eating"],
    "like": ["likes", "love", "loves", "adore", "adores", "enjoy", "enjoys",
             "favorite", "favourite", "prefer", "prefers", "into"],
    "city": ["town", "live", "lives", "living", "based", "reside", "resides",
             "moved"],
    "buy": ["bought", "buys", "purchase", "purchased", "acquired", "got"],
    "travel": ["travelled", "traveled", "went", "trip", "visit", "visited",
               "journey", "vacation"],
    "learn": ["learning", "learns", "study", "studying", "studies",
              "practicing", "picking"],
    "pet": ["animal", "adopt", "adopted", "companion"],
    "name": ["named", "called", "call"],
    "color": ["colour", "shade"],
    "hobby": ["hobbies", "pastime", "interests", "interest"],
    "when": ["month", "year", "date", "time"],
}
_CANON = {w: k for k, ws in SYNONYMS.items() for w in ws}
_WORDS = re.compile(r"\w+|[^\w\s]")


def fnv1a(text: str, mod: int) -> int:
    h = 2166136261
    for b in text.encode("utf-8"):
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h % mod


class Embedder:
    """Reference query embedding, float64 from the per-word float32 draws."""

    def __init__(self, dim: int, seed: int = 0):
        self.dim, self.seed = dim, seed
        self._cache: Dict[str, np.ndarray] = {}

    def _word(self, word: str) -> np.ndarray:
        w = word.lower()
        w = _CANON.get(w, w)
        v = self._cache.get(w)
        if v is None:
            rng = np.random.default_rng(fnv1a(w, 2 ** 31) + self.seed)
            v = rng.standard_normal(self.dim).astype(np.float32)
            self._cache[w] = v
        return v

    def __call__(self, text: str) -> np.ndarray:
        words = _WORDS.findall(text)
        if not words:
            return np.zeros(self.dim)
        v = np.mean([self._word(w).astype(np.float64) for w in words],
                    axis=0)
        n = np.linalg.norm(v)
        return v / n if n > 0 else v


def exact_topk(rows: np.ndarray, row0: int, q: np.ndarray, k: int):
    """Top-k of `rows` (the namespace's rows, global ids row0...) by exact
    float64 inner product with `q`: (global ids, all scores)."""
    s = rows.astype(np.float64) @ q
    local = np.lexsort((np.arange(s.size), -s))[:k]
    return row0 + local, s


def rrf_scores(k: int, weight: float = 1.0) -> np.ndarray:
    """Fused score of rank 0..k-1 of a single ranking, float32."""
    return np.float32(weight) / (np.float32(RRF_C)
                                 + np.arange(k, dtype=np.float32)
                                 + np.float32(1.0))


def compare(items: Sequence[dict], answers: Sequence[dict],
            vectors: np.ndarray, rows_per_tenant: int, k: int,
            embed: Embedder) -> Dict[str, float]:
    """Every answer of the window against the reference.

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
    missing = answer_errors = 0
    dense_gap = 0.0
    for it, ans in zip(items, answers):
        if not ans.get("ok") or "row_ids" not in ans:
            missing += 1
            continue
        t = it["tenant"]
        row0 = t * rows_per_tenant
        rows = vectors[row0: row0 + rows_per_tenant]
        q = embed(it["body"]["query"])
        ref, s = exact_topk(rows, row0, q, k)
        got = np.asarray(ans["row_ids"], np.int64)
        local = got - row0
        w = float(it["body"].get("dense_weight", 1.0))
        want = rrf_scores(ref.size, w).astype(np.float64)
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


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[name] <= limits[name] for name in limits)


def check_lines(numbers: Dict[str, float], limits: Dict[str, float]
                ) -> List[str]:
    return [f"check {name}={numbers[name]!r} limit={limits[name]!r}"
            for name in limits]
