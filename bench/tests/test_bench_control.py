"""The control -- the reference in the program's place, one precision step
below the configuration's -- comes out not correct at a size a test can
hold, while answers at the configuration's precision come out correct."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import control  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
from traffic import schedule  # noqa: E402

CONTROLS = {"rank-f32": ["high"], "rank-int8": ["int4_high", "int8_norescore"]}


def _readings(workload, seed, rank):
    cell = spec.resolve(workload)
    c = dict(cell.config, tenants=8)
    vectors = data.bulk_vectors(seed, c["tenants"] * c["rows_per_tenant"],
                                c["dim"])
    items = schedule(cell.traffic, c, seed, 1.5)
    ans = control.control_answers(items, vectors, c["rows_per_tenant"],
                                  c["pool"], rank, control.embed_f32(c["dim"]))
    got = reference.compare(items, ans, vectors, c["rows_per_tenant"],
                            c["pool"], reference.Embedder(c["dim"]))
    return got, c["limits"]


@pytest.mark.parametrize("workload", sorted(CONTROLS))
def test_configured_precision_is_correct(workload):
    exact = lambda rows, q: control._topk(rows @ q, 64)   # noqa: E731
    got, limits = _readings(workload, 7, exact)
    assert reference.verdict(got, limits), got


@pytest.mark.parametrize("workload,mode",
                         [(w, m) for w, ms in CONTROLS.items() for m in ms])
def test_control_is_not_correct(workload, mode):
    got, limits = _readings(workload, 7,
                            control.ranker(mode, 64, control.dot_high_np))
    assert not reference.verdict(got, limits), got
    assert got["dense_gap"] > limits["dense_gap"]


def test_bf16_rounding():
    x = np.array([1.0, 1.0 + 2 ** -9, 1.0 + 3 * 2 ** -9, -3.14159],
                 np.float32)
    # 8 significant bits: ties and below-half round down, above-half up
    assert control.bf16(x).tolist() == [1.0, 1.0, 1.0078125, -3.140625]
