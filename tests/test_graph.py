"""Device-resident memory graph (core/graph.py) and its RetrievalPlan
stage: batched k-hop expansion vs the scalar BFS oracle (exact ids, order
and float32 scores) under interleaved mutation, zero-recompile/zero-upload
steady state, namespace isolation, durability (snapshot/restore + WAL
replay bit-identity) and the store alignment invariants."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.checkpoint import io as ckpt_io
from repro.common.utils import count_compiles
from repro.core import graph as graph_mod
from repro.core.api import RetrievalPlan, RetrieveRequest
from repro.core.embedder import HashEmbedder
from repro.core.extraction import Message
from repro.core.graph import (EDGE_CAUSAL, EDGE_ENTITY, EDGE_TEMPORAL,
                              GraphInvariantError, MemoryGraph)
from repro.core.service import MemoryService
from repro.core.store import MemoryStore, StoreInvariantError
from repro.core.triples import Triple, TripleStore, normalize_entity
from repro.kernels.ref import graph_expand_ref

EMB = HashEmbedder()

PEOPLE = ["Caroline", "Dave", "Mel"]
TEXTS = [
    "I adopted a cat named Muffin.",
    "Muffin is allergic to peanuts.",
    "I work as a teacher.",
    "I work as a nurse.",
    "I went to Banff. I started aikido classes.",
    "My favorite color is teal.",
    "I live in Lisbon.",
    "I bought a camera.",
    "I am learning the cello.",
]


def _store(**kw):
    return MemoryStore(EMB, **kw)


def _fill(store, namespaces=("u1", "u2"), sessions=3, rng=None):
    rng = rng or np.random.default_rng(0)
    for ns in namespaces:
        for s in range(sessions):
            msgs = [Message(str(rng.choice(PEOPLE)), str(rng.choice(TEXTS)))
                    for _ in range(3)]
            store.ingest(ns, f"s{s}", msgs)
    return store


def _expand_both(store, queries, namespaces, hops_b, k=16, max_hops=2,
                 seed_k=8, decay=0.5, tw=None):
    """Run the device expansion AND the scalar oracle on identical inputs;
    returns ((ids, scores), (oracle_ids, oracle_scores))."""
    g = store.graph
    q_ns = np.asarray([store.tenant(ns).ns_id for ns in namespaces],
                      np.int32)
    if tw is None:
        tw = np.tile(np.asarray([[1.0, 0.9, 0.9]], np.float32),
                     (len(queries), 1))
    qv = np.asarray(EMB.embed_texts(list(queries)), np.float32)
    _, dense_ids = store.vindex.search_batch(qv, q_ns, k=8)
    _, sparse_ids = store.bm25.topk_batch_dev(list(queries), k=8,
                                              namespaces=list(q_ns))
    rankings = [np.asarray(dense_ids), np.asarray(sparse_ids)]
    ids, scores = g.expand(rankings, q_ns,
                           store.row_namespaces_device(), tw,
                           np.asarray(hops_b, np.int32), k=k,
                           max_hops=max_hops, seed_k=seed_k, decay=decay)
    row_labels = np.asarray(store.row_namespaces_device())
    es, ed, et, ew = g.edges()
    rs, ro = g.row_incidence()
    oids, oscores = graph_expand_ref(
        es, ed, et, ew, g.node_ns(), rs, ro, row_labels, rankings, q_ns,
        tw, np.asarray(hops_b, np.int32), hops=max_hops, k=k,
        seed_k=seed_k, decay=decay)
    return ((np.asarray(ids), np.asarray(scores, np.float32)),
            (oids, oscores))


def _assert_parity(store, queries, namespaces, hops_b, **kw):
    (ids, scores), (oids, oscores) = _expand_both(
        store, queries, namespaces, hops_b, **kw)
    np.testing.assert_array_equal(ids, oids)
    np.testing.assert_array_equal(scores, oscores)   # exact f32, not close


# -- satellite: Triple.key normalization --------------------------------------

def test_triple_key_normalizes_case_and_whitespace():
    assert normalize_entity("  Caroline\t Smith ") == "caroline smith"
    t1 = Triple("Caroline", "Works As", "teacher", timestamp=1.0)
    t2 = Triple("caroline ", " works  as", "nurse", timestamp=2.0)
    assert t1.key() == t2.key() == "caroline|works as"


def test_latest_for_key_on_mixed_case_duplicates():
    """Aliased subjects ("Caroline" vs "caroline") are ONE version chain:
    latest_for_key resolves across them and superseded_ids retires the
    older spelling — before the fix they silently split into two chains."""
    ts = TripleStore()
    a = ts.add(Triple("Caroline", "works as", "teacher", timestamp=1.0))
    ts.add(Triple("caroline", "Works as", "nurse", timestamp=2.0))
    latest = ts.latest_for_key("caroline|works as")
    assert latest is not None and latest.object == "nurse"
    assert ts.superseded_ids() == [a]
    assert len(ts.versions(a)) == 2


# -- graph construction -------------------------------------------------------

def test_ingest_builds_entity_temporal_causal_edges():
    store = _store()
    store.ingest("u1", "s1", [
        Message("Caroline", "I adopted a cat named Muffin."),
        Message("Caroline", "I work as a teacher."),
    ])
    store.ingest("u1", "s2", [Message("Caroline", "I work as a nurse.")])
    g = store.graph
    n = {t: i for i, t in enumerate(g._node_text)}
    es, ed, et, _ = g.edges()
    edges = set(zip(es.tolist(), ed.tolist(), et.tolist()))
    # entity: subject <-> object, both directions
    assert (n["caroline"], n["cat"], EDGE_ENTITY) in edges
    assert (n["cat"], n["caroline"], EDGE_ENTITY) in edges
    # temporal: consecutive triples' objects within one session
    assert (n["cat"], n["muffin"], EDGE_TEMPORAL) in edges \
        or (n["muffin"], n["teacher"], EDGE_TEMPORAL) in edges
    # causal: the "works as" version chain links teacher -> nurse
    assert (n["teacher"], n["nurse"], EDGE_CAUSAL) in edges
    assert (n["nurse"], n["teacher"], EDGE_CAUSAL) in edges
    # CSR offsets cover every edge exactly once
    offs = g.csr_offsets()
    assert offs[-1] == g.n_edges and len(offs) == g.n_nodes + 1


def test_interning_collapses_aliases_and_separates_namespaces():
    g = MemoryGraph()
    a = g.intern(0, "Caroline")
    assert g.intern(0, "  caroline ") == a
    assert g.intern(1, "Caroline") != a          # same text, other tenant
    assert g.node_ns().tolist() == [0, 1]


def test_row_alignment_drift_raises_store_invariant_error():
    store = _fill(_store(), sessions=1)
    store.graph._n_rows -= 1                     # simulate lane drift
    with pytest.raises(StoreInvariantError):
        store.ingest("u1", "sX", [Message("Mel", "I live in Lisbon.")])


def test_compact_map_size_mismatch_raises():
    g = MemoryGraph()
    g.append_row(0, -1, -1)
    with pytest.raises(GraphInvariantError):
        g.compact_rows(np.asarray([0, 1], np.int64))
    with pytest.raises(GraphInvariantError):
        g.append_row(5, -1, -1)                  # out-of-order row append


# -- expansion == oracle ------------------------------------------------------

def test_expansion_matches_oracle_basic():
    store = _fill(_store())
    _assert_parity(store, ["allergic", "camera", "nurse"],
                   ["u1", "u2", "u1"], [2, 1, 2])


def test_expansion_matches_oracle_after_evict_and_compact():
    store = _fill(_store())
    store.evict_superseded("u1")
    _assert_parity(store, ["nurse", "Banff"], ["u1", "u2"], [2, 2])
    store.evict_namespace("u2")
    _assert_parity(store, ["nurse", "Banff"], ["u1", "u2"], [2, 2])
    store.compact()
    _assert_parity(store, ["nurse", "Banff"], ["u1", "u1"], [3, 1],
                   max_hops=4)


def test_expansion_matches_oracle_after_restore(tmp_path):
    store = _fill(_store())
    p = str(tmp_path / "snap.ckpt")
    store.snapshot(p)
    restored = MemoryStore.restore(p, EMB)
    a = _expand_both(store, ["allergic"], ["u1"], [2])
    b = _expand_both(restored, ["allergic"], ["u1"], [2])
    np.testing.assert_array_equal(a[0][0], b[0][0])     # device == device
    np.testing.assert_array_equal(a[0][1], b[0][1])     # bit-identical
    _assert_parity(restored, ["allergic"], ["u1"], [2])
    # and the restored graph keeps growing the same version chains
    restored.ingest("u1", "s9", [Message("Caroline", "I work as a chef.")])
    store.ingest("u1", "s9", [Message("Caroline", "I work as a chef.")])
    assert restored.graph.edge_type_counts() == \
        store.graph.edge_type_counts()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_expansion_matches_oracle_interleaved(seed):
    """add / evict / compact / snapshot-restore interleaved, parity checked
    after every step (the deterministic core of the property test below)."""
    rng = np.random.default_rng(seed)
    store = _fill(_store(), sessions=2, rng=rng)

    def check():
        qs = [str(rng.choice(TEXTS)).split()[-1] for _ in range(3)]
        nss = [str(rng.choice(["u1", "u2", "ghost"])) for _ in range(3)]
        hops = rng.integers(1, 4, size=3).tolist()
        _assert_parity(store, qs, nss, hops, max_hops=4,
                       seed_k=int(rng.integers(1, 9)))

    check()
    store.ingest("u1", "sA", [Message("Dave", str(rng.choice(TEXTS)))])
    check()
    store.evict_superseded("u1")
    check()
    store.compact()
    check()
    store.ingest("u2", "sB", [Message("Mel", str(rng.choice(TEXTS)))
                              for _ in range(2)])
    check()


try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                               # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    _OPS = st.lists(
        st.one_of(
            st.tuples(st.just("ingest"), st.integers(0, 1),
                      st.lists(st.integers(0, len(TEXTS) - 1), min_size=1,
                               max_size=3)),
            st.tuples(st.just("evict_superseded"), st.integers(0, 1),
                      st.just([])),
            st.tuples(st.just("evict_ns"), st.integers(0, 1), st.just([])),
            st.tuples(st.just("compact"), st.just(0), st.just([])),
            st.tuples(st.just("restore"), st.just(0), st.just([])),
        ), min_size=1, max_size=6)

    @given(_OPS, st.integers(1, 3), st.integers(1, 8))
    @settings(max_examples=12, deadline=None)
    def test_property_kernel_equals_bfs_oracle(ops, hops, seed_k):
        """Hypothesis: under ANY interleaving of ingest / evict / compact /
        snapshot-restore, the batched k-hop kernel returns exactly the
        scalar BFS oracle's ids, order and float32 scores."""
        import tempfile
        store = _store()
        nss = ("u1", "u2")
        si = 0
        for op, tenant, texts in ops:
            ns = nss[tenant]
            if op == "ingest":
                msgs = [Message(PEOPLE[i % len(PEOPLE)], TEXTS[i])
                        for i in texts]
                store.ingest(ns, f"s{si}", msgs)
                si += 1
            elif op == "evict_superseded":
                store.evict_superseded(ns)
            elif op == "evict_ns":
                store.evict_namespace(ns)
            elif op == "compact":
                store.compact()
            elif op == "restore":
                with tempfile.TemporaryDirectory() as d:
                    p = f"{d}/snap.ckpt"
                    store.snapshot(p)
                    store = MemoryStore.restore(p, EMB)
        _assert_parity(store, ["allergic teacher", "Banff camera"],
                       ["u1", "u2"], [hops, max(1, hops - 1)],
                       max_hops=4, seed_k=seed_k)


# -- steady state: zero recompiles, zero lane re-uploads ----------------------

def test_no_recompile_no_upload_while_edges_grow_within_bucket(monkeypatch):
    """The device-residency contract: while the edge lanes grow WITHIN a
    pow2 capacity bucket, steady-state expansions reuse one executable
    (zero compiles) and never re-upload a capacity-sized lane (the only
    jnp.asarray calls in the graph module are the pow2-padded deltas)."""
    g = MemoryGraph()
    for i in range(20):
        g.intern(0, f"ent{i}")
    for r in range(24):
        g.append_row(r, r % 20, (r + 1) % 20)
    for i in range(0, 16, 2):
        g.link_nodes(i, i + 1, EDGE_ENTITY)
    row_labels = jnp.asarray(np.zeros(64, np.int32))
    rankings = [np.arange(16, dtype=np.int32)[None, :].repeat(2, axis=0)]
    q_ns = np.zeros(2, np.int32)
    tw = np.ones((2, 3), np.float32)
    hops_b = np.asarray([2, 2], np.int32)

    def run():
        ids, _ = g.expand(rankings, q_ns, row_labels, tw, hops_b,
                          k=16, max_hops=2, seed_k=8, decay=0.5)
        return np.asarray(ids)

    run()                                 # materialize + compile
    g.link_nodes(16, 17, EDGE_ENTITY)     # warm the width-2 edge append
    run()
    assert g._edge_src.shape[0] == 64     # still in the first bucket

    uploads = []
    real_asarray = graph_mod.jnp.asarray

    def spy_asarray(x, *a, **kw):
        if getattr(x, "nbytes", 0) >= 64 * 4:
            uploads.append(np.shape(x))
        return real_asarray(x, *a, **kw)

    monkeypatch.setattr(graph_mod.jnp, "asarray", spy_asarray)
    with count_compiles() as cc:
        for i in range(8):
            g.link_nodes(17 + (i % 2), i % 16, EDGE_TEMPORAL)
            run()
    assert cc.count == 0, f"recompiled {cc.count}x: {cc.msgs[:3]}"
    assert uploads == [], f"lane-sized host->device transfers: {uploads}"
    assert g.n_edges <= 64                # never left the bucket


def test_growth_across_bucket_recompiles_then_restabilizes():
    g = MemoryGraph()
    for i in range(8):
        g.intern(0, f"e{i}")
    g.append_row(0, 0, 1)
    row_labels = jnp.asarray(np.zeros(64, np.int32))
    args = ([np.asarray([[0]], np.int32)], np.zeros(1, np.int32),
            row_labels, np.ones((1, 3), np.float32),
            np.asarray([2], np.int32))

    def run():
        return np.asarray(g.expand(*args, k=8, max_hops=2, seed_k=4,
                                   decay=0.5)[0])

    run()
    for i in range(40):                   # blow through the 64-edge bucket
        g.link_nodes(i % 8, (i + 3) % 8, i % 3)
    assert g.n_edges > 64 or g._edge_src.shape[0] == 64
    run()                                 # recompile at the new capacity
    with count_compiles() as cc:
        g.link_nodes(0, 5, EDGE_CAUSAL)
        run()
    assert cc.count == 0


# -- namespace isolation ------------------------------------------------------

def test_expansion_never_crosses_namespaces():
    store = _store()
    for ns in ("u1", "u2"):
        store.ingest(ns, "s0", [
            Message("Caroline", "I adopted a cat named Muffin."),
            Message("Caroline", "Muffin is allergic to peanuts."),
        ])
    t1, t2 = store.tenant("u1"), store.tenant("u2")
    rows_u2 = set(t2.rows)
    # seed_k=1 so only the best seed row's nodes seed the walk and the
    # rest of the chain must be DISCOVERED (seed nodes never score rows)
    (ids, scores), _ = _expand_both(
        store, ["Muffin allergic"], ["u1"], [3], max_hops=4, seed_k=1)
    hit = set(int(r) for r in ids[0] if r >= 0)
    assert hit and not (hit & rows_u2)
    assert all(int(store.vindex.row_namespaces()[r]) == t1.ns_id
               for r in hit)
    # same surface through the service: u1's graph-expanded retrieval only
    # ever renders u1's triples
    svc = MemoryService(store=store)
    ctx = svc.retrieve("u1", "what is Muffin allergic to",
                       stages=("dense", "sparse", "graph", "budget"))
    assert all(tr.conversation_id == "u1" for tr in ctx.triples)


# -- durability ---------------------------------------------------------------

def test_graph_survives_snapshot_restore_bit_identical(tmp_path):
    store = _fill(_store())
    store.link("u1", "Muffin", "vet visits", "causal", weight=0.8)
    p = str(tmp_path / "snap.ckpt")
    store.snapshot(p)
    r = MemoryStore.restore(p, EMB)
    g1, g2 = store.graph, r.graph
    assert g1._node_text == g2._node_text
    np.testing.assert_array_equal(g1.node_ns(), g2.node_ns())
    for x, y in zip(g1.edges(), g2.edges()):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(g1.row_incidence(), g2.row_incidence()):
        np.testing.assert_array_equal(x, y)
    assert g1._tail == g2._tail and g1._edge_idx == g2._edge_idx


def test_restore_refuses_misaligned_graph_lanes(tmp_path):
    store = _fill(_store(), sessions=1)
    p = str(tmp_path / "snap.ckpt")
    store.snapshot(p)
    arrays = ckpt_io.load_raw(p)
    arrays["graph_row_sub"] = arrays["graph_row_sub"][:-1]
    arrays["graph_row_obj"] = arrays["graph_row_obj"][:-1]
    p2 = str(tmp_path / "tampered.ckpt")
    ckpt_io.save(p2, dict(arrays))
    with pytest.raises(StoreInvariantError):
        MemoryStore.restore(p2, EMB)


def test_graph_edge_wal_record_replays_bit_identical(tmp_path):
    """link() journals BEFORE applying; replaying the captured records into
    a fresh store rebuilds the exact same graph lanes."""
    records = []
    store = _store()
    store.wal_sink = records.append
    _fill(store, sessions=2)
    store.link("u1", "Caroline", "marathon training", "entity")
    store.link("u1", "marathon training", "knee injury", "causal",
               weight=0.5)
    assert any(r["op"] == "graph_edge" for r in records)
    replayed = _store()
    for r in records:
        replayed.apply_wal(r)
    g1, g2 = store.graph, replayed.graph
    assert g1._node_text == g2._node_text
    for x, y in zip(g1.edges(), g2.edges()):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(g1.row_incidence(), g2.row_incidence()):
        np.testing.assert_array_equal(x, y)
    _assert_parity(replayed, ["marathon"], ["u1"], [2])


def test_link_validates_edge_type():
    store = _store()
    with pytest.raises(ValueError):
        store.link("u1", "a", "b", "telepathic")


# -- the service stage --------------------------------------------------------

def test_graph_stage_mixed_batch_matches_solo_execution():
    """A batch where only SOME requests run the graph stage: every request
    answers exactly like the same request executed alone (the expanded
    ranking is masked to -1 for the others)."""
    svc = MemoryService(store=_fill(_store()))
    reqs = [
        RetrieveRequest("u1", "allergic", stages=("dense", "sparse",
                                                  "graph"), hops=2),
        RetrieveRequest("u2", "camera"),
        RetrieveRequest("u1", "nurse",
                        stages=("dense", "sparse", "graph"), hops=1,
                        edge_weights=(1.0, 0.5, 2.0), graph_weight=1.5),
    ]
    plan = RetrievalPlan.raw()
    batched = svc.execute(reqs, plan=plan)
    for req, got in zip(reqs, batched):
        solo = svc.execute([req], plan=plan)[0]
        assert got.row_ids == solo.row_ids
        assert got.scores == solo.scores


def test_graph_stage_changes_ranking_and_surfaces_chain():
    """The acceptance shape: a 2-hop chain fact (pet -> name -> allergen)
    that flat hybrid retrieval misses is surfaced by the graph plan."""
    svc = MemoryService(EMB, top_k=5)
    svc.record("u1", "s0", [
        Message("Caroline", "I adopted a cat named Muffin."),
        Message("Caroline", "My favorite color is teal."),
    ])
    svc.record("u1", "s1", [
        Message("Caroline", "Muffin is allergic to peanuts."),
    ])
    for i in range(16):   # noise rows so flat top-k has competition and
        # the seed window doesn't blanket the whole (tiny) graph
        svc.record("u1", f"n{i}", [Message("Dave", TEXTS[i % len(TEXTS)])])
    q = "What food can Caroline's cat never eat?"
    flat = svc.execute([RetrieveRequest("u1", q)],
                       plan=RetrievalPlan.raw())[0]
    # graph_seed_k=2: the chain HEAD ("cat is named muffin") seeds the
    # walk but the answer row does not — it must be discovered via the
    # muffin -> peanuts edge (seeded rows never score, so a wide seed
    # window over a tiny corpus would leave nothing to discover)
    graph = svc.execute([RetrieveRequest("u1", q, hops=2)],
                        plan=RetrievalPlan.graph_expanded(
                            budget=False, graph_seed_k=2))[0]
    t = svc.store.get("u1")

    def texts(raw):
        return [t.triples.get(tid).text() for tid in raw.triple_ids]
    target = "Muffin is allergic to peanuts"
    assert any(target in x for x in texts(graph))
    assert texts(graph) != texts(flat)


def test_graph_plan_validation():
    with pytest.raises(ValueError):
        RetrievalPlan(stages=("graph", "fuse"))      # no seed stage
    with pytest.raises(ValueError):
        RetrieveRequest("u1", "q", hops=0)
    with pytest.raises(ValueError):
        RetrieveRequest("u1", "q", edge_weights=(1.0, 1.0))
    with pytest.raises(ValueError):
        RetrievalPlan(graph_decay=0.0)
    assert RetrievalPlan.graph_expanded().wants_graph
    assert not RetrievalPlan().wants_graph           # opt-in, not default


# -- telemetry ----------------------------------------------------------------

def test_graph_span_and_metrics_in_scrape():
    """plan.graph span attrs (launches, compiled hops, graph size) in the
    trace tree and the memori_graph_* gauges in the Prometheus scrape."""
    from repro.obs.telemetry import Telemetry, set_telemetry, walk_spans
    from repro.serving.frontend import flatten_metrics
    tel = Telemetry()
    set_telemetry(tel)
    try:
        svc = MemoryService(store=_fill(_store()))
        tr = tel.start_trace(op="retrieve")
        with tel.activate([tr]):
            svc.execute([RetrieveRequest("u1", "allergic", hops=2)],
                        plan=RetrievalPlan.graph_expanded(budget=False))
        tel.finish_trace(tr)
        spans = {s["name"]: s for s in walk_spans(tr.to_dict()["root"])}
        g = spans["plan.graph"]["attrs"]
        assert g["launches"] == 1
        assert g["hops_compiled"] == 2
        assert g["edges"] == svc.store.graph.n_edges
        # gauges ride the stats() flattening used by /v1/metrics
        names = {n for n, _ in flatten_metrics(svc.stats())}
        for want in ("memori_graph_nodes", "memori_graph_edges",
                     "memori_graph_edges_causal",
                     "memori_graph_rows_with_incidence"):
            assert want in names, f"missing gauge {want}"
    finally:
        set_telemetry(Telemetry())
