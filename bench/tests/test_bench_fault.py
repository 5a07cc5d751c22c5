"""A whole run, driven at a tiny size on the CPU with the chip check
skipped: sound, it comes out correct; with an answer altered where the
timed path produces it, it comes out not correct."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cell as cell_mod  # noqa: E402
import spec  # noqa: E402


@pytest.fixture
def tiny_cell(monkeypatch, tmp_path):
    c = spec.resolve("rank-f32")
    c.config = dict(c.config, tenants=4, rows_per_tenant=64,
                    host_demo=True, max_len=32, max_batch=2)
    c.traffic = dict(c.traffic, rate_per_s=12.0, workers=4)
    monkeypatch.setattr(cell_mod, "CACHE_DIR", tmp_path)
    monkeypatch.setattr(cell_mod, "load_peaks",
                        lambda kind: spec.load_peaks("TPU v5 lite"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield c
    for k, v in saved.items():
        jax.config.update(k, v)


def _altered(search_batch):
    """The dense stage's answer with its best two rows swapped."""
    def search(self, queries, q_ns, k):
        s, i = search_batch(self, queries, q_ns, k)
        i = np.asarray(i).copy()
        i[:, [0, 1]] = i[:, [1, 0]]
        return s, i
    return search


@pytest.mark.parametrize("fault", [None, "answer_altered"])
def test_run_detects_a_broken_timed_path(tiny_cell, monkeypatch, fault):
    from repro.core.vector_index import VectorIndex
    if fault == "answer_altered":
        monkeypatch.setattr(VectorIndex, "search_batch",
                            _altered(VectorIndex.search_batch))
    out = cell_mod.run(tiny_cell, 2 ** 31 + 99, 1.0, trace=False,
                       t_start=time.time())
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["correct"] is (fault is None)
    assert list(out)[-1] == "checks"
    if fault:
        assert out["checks"]["dense_gap"]["value"] > \
            out["checks"]["dense_gap"]["limit"]
