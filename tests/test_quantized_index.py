"""Quantized device bank + hot/warm tiered residency (ISSUE 7):
quantize→dequantize round-trip invariants, recall@k vs the f32 oracle on
benign and adversarial distributions, snapshot→restore equivalence, the
zero-recompile / zero-upload residency spies with quantization and tiering
enabled, the cached-labels zero-allocation regression, and the TierManager
policy unit tests."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.utils import count_compiles
from repro.core import vector_index as vi_mod
from repro.core.embedder import HashEmbedder
from repro.core.extraction import Message
from repro.core.service import MemoryService
from repro.core.store import MemoryStore
from repro.core.tiering import TierManager, TierPolicy
from repro.core.vector_index import VectorIndex, quantize_rows_np
from repro.kernels import ref as kref

RNG = np.random.default_rng(17)


def _f32_oracle_ids(vi, q, q_ns, k):
    """Masked top-k recomputed from the FULL-PRECISION host mirror."""
    eff = np.where(vi.alive(), vi.row_namespaces(), -1)
    _, i = kref.topk_mips_masked_ref(
        jnp.asarray(q), jnp.asarray(vi.bank), jnp.asarray(q_ns, jnp.int32),
        jnp.asarray(eff, jnp.int32), k=min(k, vi.n))
    return np.asarray(i, np.int64)


def _recall(got, want):
    """Mean per-row overlap of live ids."""
    per = []
    for g, w in zip(got, want):
        w = set(int(x) for x in w if x >= 0)
        if not w:
            continue
        g = set(int(x) for x in g if x >= 0)
        per.append(len(g & w) / len(w))
    return float(np.mean(per)) if per else 1.0


# -- quantization round-trip invariants ---------------------------------------

def test_quantize_rows_np_matches_ref_bitwise():
    """The host quantizer (append/promote path) and the jnp ref (oracle +
    materialization contract) must agree bit-for-bit, including the
    zero-row and denormal-ish edge cases."""
    bank = RNG.standard_normal((128, 48)).astype(np.float32)
    bank[3] = 0.0
    bank[7] *= 1e-5
    bank[11] *= 1e4
    c_np, s_np = quantize_rows_np(bank)
    c_ref, s_ref = kref.quantize_rows_ref(bank)
    np.testing.assert_array_equal(c_np, np.asarray(c_ref))
    np.testing.assert_array_equal(s_np, np.asarray(s_ref))


def test_quantize_roundtrip_error_bound_per_row():
    bank = RNG.standard_normal((200, 64)).astype(np.float32) * \
        np.exp(RNG.uniform(-8, 8, size=(200, 1))).astype(np.float32)
    codes, scales = quantize_rows_np(bank)
    recon = codes.astype(np.float32) * scales[:, None]
    assert (np.abs(recon - bank) <= scales[:, None] / 2 + 1e-7).all()


@pytest.mark.parametrize("distribution", ["clustered", "adversarial"])
def test_quantized_search_recall_vs_f32_oracle(distribution):
    """End-to-end recall@10 of the quantized index (fused dequant search +
    exact f32 rescore) vs the f32 oracle must stay >= 0.95 — on a benign
    clustered distribution AND an adversarial one mixing tiny-norm rows
    (scale underflow pressure) with huge-norm outliers (score dominance)."""
    dim, n, k = 48, 600, 10
    if distribution == "clustered":
        centers = RNG.standard_normal((6, dim)).astype(np.float32) * 3
        vecs = (centers[RNG.integers(0, 6, n)]
                + 0.3 * RNG.standard_normal((n, dim))).astype(np.float32)
    else:
        vecs = RNG.standard_normal((n, dim)).astype(np.float32)
        vecs[::11] *= 1e-4                  # tiny-norm rows
        vecs[::17] *= 1e3                   # huge-norm outliers
    ns = RNG.integers(0, 4, n)
    vi_q = VectorIndex(dim=dim, use_kernel=True, quantize="int8", rescore=4)
    vi_q.add(vecs, ns)
    q = RNG.standard_normal((12, dim)).astype(np.float32)
    q_ns = np.arange(12) % 4
    _, i_q = vi_q.search_batch(q, q_ns, k=k)
    want = _f32_oracle_ids(vi_q, q, q_ns, k)
    rec = _recall(np.asarray(i_q), want)
    assert rec >= 0.95, f"recall@{k} = {rec} on {distribution}"


def test_quantized_scores_are_exact_f32():
    """The rescore contract: every score leaving the quantized index is the
    EXACT f32 inner product (quantization can cost recall, never score
    fidelity)."""
    dim = 32
    vi = VectorIndex(dim=dim, use_kernel=True, quantize="int8")
    vecs = RNG.standard_normal((300, dim)).astype(np.float32)
    vi.add(vecs, RNG.integers(0, 3, 300))
    q = RNG.standard_normal((6, dim)).astype(np.float32)
    q_ns = np.arange(6) % 3
    s, i = vi.search_batch(q, q_ns, k=8)
    s, i = np.asarray(s), np.asarray(i)
    for r in range(6):
        for j in range(8):
            if i[r, j] >= 0:
                exact = float(np.float32(q[r]) @ vecs[i[r, j]])
                np.testing.assert_allclose(s[r, j], exact, rtol=1e-5,
                                           atol=1e-5)


def test_quantized_incremental_updates_match_fresh_materialization():
    """add/delete/compact through the donated in-place int8 buffers must
    answer exactly like a fresh index materialized from the same host
    mirror (the dual-buffer invariant)."""
    dim, k = 24, 6
    vi = VectorIndex(dim=dim, capacity=64, use_kernel=True, quantize="int8")
    q = RNG.standard_normal((4, dim)).astype(np.float32)
    q_ns = np.asarray([0, 1, 2, 0], np.int32)

    def check():
        fresh = VectorIndex(dim=dim, capacity=64, use_kernel=True,
                            quantize="int8")
        fresh.load_rows(vi.bank, vi.alive(), ns=vi.row_namespaces())
        _, i1 = vi.search_batch(q, q_ns, k=k)
        _, i2 = fresh.search_batch(q, q_ns, k=k)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    vi.add(RNG.standard_normal((30, dim)).astype(np.float32),
           ns=np.arange(30) % 3)
    check()
    vi.delete([2, 9, 14])
    check()
    vi.add(RNG.standard_normal((80, dim)).astype(np.float32),
           ns=np.arange(80) % 3)            # crosses a capacity boundary
    check()
    vi.delete(np.arange(20, 45))
    vi.compact()
    check()


def test_quantized_snapshot_restore_matches_pre_snapshot_truth(tmp_path):
    """Snapshots are always full-precision: writing one from a quantized
    service and restoring it (quantized again) must preserve the host
    mirror byte-for-byte and answer retrieval identically to the
    pre-snapshot service."""
    path = str(tmp_path / "snap.msgpack")
    svc = MemoryService(HashEmbedder(), use_kernel=True, quantize="int8",
                        budget=800)
    svc.record("a/c0", "s0", [
        Message("Alice", "I live in Tallinn.", 1.0),
        Message("Alice", "I adopted a hedgehog named Biscuit.", 2.0)])
    svc.record("b/c0", "s0", [
        Message("Bob", "I live in Porto.", 1.0),
        Message("Bob", "I work as a welder.", 2.0)])
    queries = [("a/c0", "Which city does the user live in?"),
               ("b/c0", "What is the user's job?"),
               ("a/c0", "What pet was adopted?")]
    before = svc.retrieve_batch(queries)
    bank_before = svc.vindex.bank.copy()
    svc.snapshot(path)
    restored = MemoryService.restore(path, HashEmbedder(), use_kernel=True,
                                     quantize="int8", budget=800)
    # the f32 ground truth survived quantized residency bit-for-bit
    np.testing.assert_array_equal(restored.vindex.bank, bank_before)
    assert restored.vindex.quantize == "int8"
    after = restored.retrieve_batch(queries)
    for got, want in zip(after, before):
        assert got.text == want.text
        assert [t.text() for t in got.triples] == \
            [t.text() for t in want.triples]


# -- residency spies: zero recompiles / zero bank uploads ---------------------

def test_row_labels_device_returns_cached_buffer_no_per_call_alloc(
        monkeypatch):
    """Regression (ISSUE 7 satellite): row_labels_device() used to .copy()
    the cached labels — one fresh device allocation per retrieve.  It must
    return the SAME cached buffer and make zero jnp.asarray calls."""
    vi = VectorIndex(dim=8, capacity=64, use_kernel=False)
    vi.add(RNG.standard_normal((10, 8)).astype(np.float32),
           ns=np.arange(10) % 2)
    first = vi.row_labels_device()           # materializes once
    calls = []
    real_asarray = vi_mod.jnp.asarray

    def spy_asarray(x, *a, **kw):
        calls.append(np.shape(x))
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(vi_mod.jnp, "asarray", spy_asarray)
    for _ in range(20):
        assert vi.row_labels_device() is first
    assert calls == [], f"per-call label allocations: {calls}"


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_no_recompile_no_bank_upload_steady_state(quantize, monkeypatch):
    """The acceptance contract survives quantization: appends + searches
    within a capacity bucket reuse one executable set and never move a
    bank-sized buffer host->device.  The spy threshold is capacity*dim
    BYTES — one int8 code-bank upload (cap*dim) trips it, and so does any
    f32 bank (4x bigger); the quantized rescore gather (Q*C*D*4, candidates
    only) stays far below it."""
    dim, cap = 32, 4096
    vi = VectorIndex(dim=dim, capacity=cap, use_kernel=False,
                     quantize=quantize, rescore=2)
    vi.add(RNG.standard_normal((100, dim)).astype(np.float32),
           ns=np.arange(100) % 4)
    q = RNG.standard_normal((4, dim)).astype(np.float32)
    q_ns = np.asarray([0, 1, 2, 3], np.int32)
    # warmup: one search and one single-row append compile the executables
    np.asarray(vi.search_batch(q, q_ns, k=8)[1])
    vi.add(RNG.standard_normal((1, dim)).astype(np.float32), ns=[0])
    np.asarray(vi.search_batch(q, q_ns, k=8)[1])

    uploads = []
    real_asarray = vi_mod.jnp.asarray

    def spy_asarray(x, *a, **kw):
        if getattr(x, "nbytes", 0) >= cap * dim:
            uploads.append((np.shape(x), getattr(x, "dtype", None)))
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(vi_mod.jnp, "asarray", spy_asarray)
    with count_compiles() as cc:
        for _ in range(40):
            vi.add(RNG.standard_normal((1, dim)).astype(np.float32), ns=[1])
            _, i = vi.search_batch(q, q_ns, k=8)
        np.asarray(i)
    assert cc.count == 0, f"recompiled {cc.count}x: {cc.msgs[:3]}"
    assert uploads == [], f"bank-sized host->device transfers: {uploads}"


def _device_reads(monkeypatch):
    """The device arrays the host reads: np.asarray of a jax.Array (on
    the CPU numpy takes its buffer directly) and int()/float() (through
    `ArrayImpl._value`), each array counted once."""
    import jax
    from jax._src.array import ArrayImpl
    reads = {}
    real_value, real_asarray = ArrayImpl._value, np.asarray

    def spy_value(self):
        reads.setdefault(id(self), self.shape)
        return real_value.fget(self)

    def spy_asarray(x, *a, **kw):
        if isinstance(x, jax.Array):
            reads.setdefault(id(x), x.shape)
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(ArrayImpl, "_value", property(spy_value))
    monkeypatch.setattr(np, "asarray", spy_asarray)
    return reads


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_block_scan_counters_and_no_added_host_sync(quantize, monkeypatch):
    """The kernel's scan counters on a tenant-clustered bank of 4 blocks
    of 512 rows: ns 0 fills block 0, ns 1 block 1, ns 2 the head of block
    2, and block 3 lies beyond n.  Each launch adds its tile x block steps
    to blocks_total and its flagged steps to blocks_scanned; stats()
    exports scan_share.  The count stays on the device: a search_batch
    reads from the device no more than it did without it (nothing in f32,
    the rescore's two reads in int8), and reading the counters does."""
    store = MemoryStore(HashEmbedder(dim=8), dim=8, quantize=quantize,
                        rescore=2)
    vi = store.vindex
    vi.add(RNG.standard_normal((1100, 8)).astype(np.float32),
           ns=[0] * 512 + [1] * 512 + [2] * 76)
    assert vi.capacity == 2048
    assert store.stats()["bank"]["scan_share"] is None
    q = RNG.standard_normal((2, 8)).astype(np.float32)
    vi.search_batch(q, [0, 2], k=4)            # one tile: blocks 0 and 2
    vi.search_batch(q[:1], [1], k=4)           # block 1
    bank = store.stats()["bank"]
    assert (bank["blocks_scanned"], bank["blocks_total"]) == (3, 8)
    assert bank["scan_share"] == 3 / 8
    vi.search(q, k=4)          # uniform: every block with a live row < n
    assert (vi.counters["blocks_scanned"], vi.counters["blocks_total"]) \
        == (6, 12)

    reads = _device_reads(monkeypatch)
    vi.search_batch(q, [0, 2], k=4)
    assert len(reads) == (0 if quantize == "none" else 2), reads
    assert vi.counters["blocks_scanned"] == 8
    assert len(reads) == (1 if quantize == "none" else 3), reads


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_uniform_search_over_full_bank_scans_every_block(quantize):
    vi = VectorIndex(dim=8, capacity=1024, quantize=quantize, rescore=2)
    vi.add(RNG.standard_normal((1024, 8)).astype(np.float32))
    vi.search(RNG.standard_normal((3, 8)).astype(np.float32), k=4)
    assert vi.counters["blocks_scanned"] == vi.counters["blocks_total"] == 2


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_tiering_demote_promote_steady_state_no_recompile_no_upload(
        quantize, monkeypatch):
    """Demotion/promotion cycles of a warmed size are in-place pow2
    scatters: zero recompiles, zero bank-sized transfers — tier churn never
    degrades the residency guarantees."""
    dim, cap = 32, 4096
    vi = VectorIndex(dim=dim, capacity=cap, use_kernel=False,
                     quantize=quantize, rescore=2)
    vi.add(RNG.standard_normal((120, dim)).astype(np.float32),
           ns=np.arange(120) % 4)
    q = RNG.standard_normal((4, dim)).astype(np.float32)
    q_ns = np.asarray([0, 1, 2, 3], np.int32)
    rows_ns0 = vi.rows_in_namespace(0)
    # warmup: one demote/promote/search cycle compiles the executables
    np.asarray(vi.search_batch(q, q_ns, k=8)[1])
    vi.demote_rows(rows_ns0)
    np.asarray(vi.search_batch(q, q_ns, k=8)[1])
    vi.promote_rows(rows_ns0)
    np.asarray(vi.search_batch(q, q_ns, k=8)[1])

    uploads = []
    real_asarray = vi_mod.jnp.asarray

    def spy_asarray(x, *a, **kw):
        if getattr(x, "nbytes", 0) >= cap * dim:
            uploads.append(np.shape(x))
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(vi_mod.jnp, "asarray", spy_asarray)
    with count_compiles() as cc:
        for _ in range(10):
            assert vi.demote_rows(rows_ns0) == len(rows_ns0)
            _, i = vi.search_batch(q, q_ns, k=8)
            assert vi.promote_rows(rows_ns0) == len(rows_ns0)
            _, i = vi.search_batch(q, q_ns, k=8)
        np.asarray(i)
    assert cc.count == 0, f"recompiled {cc.count}x: {cc.msgs[:3]}"
    assert uploads == [], f"bank-sized transfers during tier churn: {uploads}"


# -- tiered residency semantics ----------------------------------------------

@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_demote_promote_round_trip_preserves_answers(quantize):
    dim, k = 24, 8
    vi = VectorIndex(dim=dim, use_kernel=True, quantize=quantize)
    vecs = RNG.standard_normal((200, dim)).astype(np.float32)
    ns = RNG.integers(0, 4, 200)
    vi.add(vecs, ns)
    q = RNG.standard_normal((8, dim)).astype(np.float32)
    q_ns = np.arange(8) % 4
    s0, i0 = vi.search_masked(q, q_ns, ns, k=k)
    rows = vi.rows_in_namespace(1)
    assert vi.demote_rows(rows) == len(rows)
    assert vi.n_warm == len(rows)
    s1, i1 = vi.search_masked(q, q_ns, ns, k=k)
    for r in range(8):
        if q_ns[r] == 1:
            assert (i1[r] == -1).all(), "demoted namespace still surfaced"
    # host fallback answers from the full-precision mirror, warm included
    sh, ih = vi.search_host(q, q_ns, k=k)
    np.testing.assert_array_equal(ih, i0)
    assert vi.promote_rows(rows) == len(rows)
    s2, i2 = vi.search_masked(q, q_ns, ns, k=k)
    np.testing.assert_array_equal(i2, i0)
    np.testing.assert_allclose(s2, s0, rtol=1e-6)


def test_tier_state_survives_compaction():
    dim = 16
    vi = VectorIndex(dim=dim, use_kernel=False)
    vecs = RNG.standard_normal((90, dim)).astype(np.float32)
    ns = np.arange(90) % 3
    vi.add(vecs, ns)
    vi.demote_rows(vi.rows_in_namespace(2))
    warm_before = vi.n_warm
    vi.delete(vi.rows_in_namespace(0))
    vi.compact()
    assert vi.n_warm == warm_before, "compaction lost the warm tier"
    q = RNG.standard_normal((3, dim)).astype(np.float32)
    _, i = vi.search_masked(q, np.asarray([2, 1, 2]), vi.row_namespaces(),
                            k=4)
    assert (i[0] == -1).all() and (i[2] == -1).all()
    assert (i[1] >= 0).any()


def test_tier_manager_ewma_decay_and_coldest_first():
    """Policy unit test on a fake clock: activity decays with the
    configured halflife and demotion picks the coldest namespaces."""
    now = [0.0]
    vi = VectorIndex(dim=8, use_kernel=False)
    vi.add(RNG.standard_normal((40, 8)).astype(np.float32),
           ns=np.arange(40) % 4)
    tm = TierManager(vi, TierPolicy(max_hot_rows=20, halflife_s=10.0),
                     clock=lambda: now[0])
    tm.note_retrieve(0)
    tm.note_retrieve(0)
    tm.note_retrieve(1)
    assert tm.score(0) == pytest.approx(2.0)
    now[0] = 10.0                            # one halflife
    assert tm.score(0) == pytest.approx(1.0)
    assert tm.score(3) == 0.0                # never seen
    did = tm.tick()                          # 40 hot > 20 budget
    assert did["demoted_rows"] == 20 and did["demoted_ns"] == 2
    # the two never-retrieved namespaces went cold first
    assert tm.demoted_namespaces() == {2, 3}
    assert vi.n_resident == 20
    # a fallback marks ns 2; the next tick promotes it and re-demotes the
    # now-coldest resident namespace to hold the budget
    tm.note_host_fallback(2)
    assert tm.counters["host_fallbacks"] == 1
    did = tm.tick()
    assert did["promoted_ns"] == 1 and not tm.is_demoted(2)
    assert vi.n_resident <= 20


def test_tier_manager_within_budget_never_demotes():
    vi = VectorIndex(dim=8, use_kernel=False)
    vi.add(RNG.standard_normal((10, 8)).astype(np.float32),
           ns=np.arange(10) % 2)
    tm = TierManager(vi, TierPolicy(max_hot_rows=100))
    did = tm.tick()
    assert did["demoted_ns"] == 0 and vi.n_warm == 0


def test_service_host_fallback_and_promotion_cycle():
    """Service-level: retrieving a demoted namespace transparently answers
    from the host mirror (same triples as when hot), counts a fallback,
    and the next maintenance tick promotes the namespace back."""
    from repro.core.lifecycle import LifecyclePolicy
    svc = MemoryService(HashEmbedder(), use_kernel=False, quantize="int8",
                        budget=800,
                        policy=LifecyclePolicy(
                            tier=TierPolicy(max_hot_rows=4)))
    svc.runtime._stop.set()                  # drive maintenance manually
    for u, city in enumerate(["Tallinn", "Porto", "Cusco"]):
        svc.record(f"u{u}/c0", "s0", [
            Message(f"U{u}", f"I live in {city}.", 1.0),
            Message(f"U{u}", "I work as a welder.", 2.0)])
    q = "Which city does the user live in?"
    hot_answers = {u: svc.retrieve(f"u{u}/c0", q).text for u in range(3)}
    svc.runtime.run_maintenance_once()       # forces demotions (budget 4)
    tiers = svc.store.tiers
    assert tiers.demoted_namespaces(), "nothing demoted despite tiny budget"
    demoted_ns = next(iter(tiers.demoted_namespaces()))
    name = next(ns for ns, t in svc.store._tenants.items()
                if t.ns_id == demoted_ns)
    got = svc.retrieve(name, q)
    assert got.text == hot_answers[int(name[1])], \
        "host fallback answered differently from the hot path"
    assert tiers.counters["host_fallbacks"] >= 1
    svc.runtime.run_maintenance_once()
    assert not tiers.is_demoted(demoted_ns)
    assert svc.retrieve(name, q).text == hot_answers[int(name[1])]
    svc.close()
