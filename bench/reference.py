"""The plain reference's shared parts, and the verdict that decides
`correct`.

Imports nothing of the program.  The reference holds its own copy of the
data (the seeded bulk vectors of bench/data.py) and of the query
embedding's semantics (a hashed bag-of-words: per-word Gaussian vectors
from the word's FNV-1a hash, synonyms folded, mean, L2-normalised), and
computes in float64:

* dense: exact maximum-inner-product top-k over the query's namespace,
  ties to the lower row id;
* fuse: weighted reciprocal-rank fusion of a ranking, score
  w / (60 + rank + 1) in float32 as the served API defines it.

Each mix's `compare` (bench/mixes/<kind>.py) reads every answer of the
window with these and returns the numbers that are held to the
configuration's limits.
"""
from __future__ import annotations

import re
from typing import Dict, List

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


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[name] <= limits[name] for name in limits)


def check_lines(numbers: Dict[str, float], limits: Dict[str, float]
                ) -> List[str]:
    return [f"check {name}={numbers[name]!r} limit={limits[name]!r}"
            for name in limits]
