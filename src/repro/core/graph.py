"""MemoryGraph — the device-resident entity graph over the triple store.

The paper's bet is that memory quality comes from *structured*
representations, yet flat top-k retrieval never traverses the structure it
already extracts: triples name entities and version chains, sessions order
facts in time.  This module packs that structure into device-resident
adjacency lanes next to the bank and turns retrieval's seed rows into a
batched k-hop expansion — the `graph` stage of RetrievalPlan.

**Nodes** are interned entities: one node per (namespace id, normalized
entity text), where normalization is `triples.normalize_entity` (the same
canonicalization `Triple.key` uses, so aliased mentions collapse to one
node).  Interning is per-namespace by construction — no edge can ever
connect two tenants, which is the first layer of the namespace-isolation
guarantee (the expansion kernel masks by node and row namespace anyway).

**Edges** are typed and directed (every upsert inserts both directions):

* ``entity`` (0)   — subject ↔ object of every triple (co-occurrence),
* ``temporal`` (1) — consecutive triples' object nodes within one session's
  extraction order (succession: "went to X" then "started Y"),
* ``causal`` (2)   — version chains: when a triple supersedes an earlier
  value of the same `Triple.key`, the old object links to the new one
  ("used to be a teacher" → "is a nurse").

**Row incidence lanes** map every global bank row to its subject/object
node ids (-1 when a row's text interned no entity), so seed rows become
seed nodes and expanded node activations become an expanded row ranking.
Row lanes are remapped through `compact()` exactly like row ids everywhere
else in the store; node/edge lanes are append-only (evicting rows removes
them from every ranking via the bank's -1 labels, but the entities they
mentioned remain traversable — an entity does not un-exist when one mention
of it is evicted).

**Device residency** follows `core/vector_index.py` to the letter: host
mirrors are the source of truth (snapshot/compact/oracle), the device lanes
live in capacity-doubling pow2 buffers updated in place by donated jitted
appends with pow2-padded update widths, and the live counts ride into the
expansion as traced scalars — so the steady state issues zero recompiles
and zero lane re-uploads while the graph grows within a capacity bucket
(spy-asserted in tests/test_graph.py).

**Expansion semantics** (`expand`, oracle: `kernels/ref.graph_expand_ref`):
seed rows activate their incident nodes at 1.0; each hop relaxes every edge
once —

    contribution(dst) = ((F[src] * (type_w[b, type] * edge_w)) * decay)
                        / out_degree(src)

— combined by max (best-path / max-product semiring), so the batched
scatter-max is order-independent and matches the scalar BFS oracle
bit-exactly in float32 (the explicit multiply order above is part of the
contract).  The degree normalization damps hub nodes (a speaker who said
forty things) so specific chains outrank hub fan-out.  A row's score is the
max over its incident nodes' activations, masked to the request's
namespace; rows rank by (-score, row id) — the store-wide lexicographic
tie-break.  Per-request hop counts ride in as a traced vector (requests in
one batch may expand to different depths inside one set of launches);
the hop loop is unrolled at a pow2-bucketed static depth.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import next_pow2 as _next_pow2
from repro.core.triples import normalize_entity

EDGE_ENTITY = 0
EDGE_TEMPORAL = 1
EDGE_CAUSAL = 2
N_EDGE_TYPES = 3
EDGE_TYPE_NAMES = ("entity", "temporal", "causal")
EDGE_TYPE_IDS = {n: i for i, n in enumerate(EDGE_TYPE_NAMES)}


def _next_capacity(n: int, floor: int = 64) -> int:
    return max(floor, _next_pow2(max(1, n)))


# ---------------------------------------------------------------------------
# Device-side primitives: donated in-place lane updates (the vector index's
# append idiom — jit cache keyed on (capacity, padded update width) only).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _dev_append_nodes(node_ns, ns_new, start):
    return jax.lax.dynamic_update_slice(node_ns, ns_new, (start,))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _dev_append_edges(src, dst, et, w, s_new, d_new, t_new, w_new, start):
    src = jax.lax.dynamic_update_slice(src, s_new, (start,))
    dst = jax.lax.dynamic_update_slice(dst, d_new, (start,))
    et = jax.lax.dynamic_update_slice(et, t_new, (start,))
    w = jax.lax.dynamic_update_slice(w, w_new, (start,))
    return src, dst, et, w


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_append_rows(rs, ro, s_new, o_new, start):
    rs = jax.lax.dynamic_update_slice(rs, s_new, (start,))
    ro = jax.lax.dynamic_update_slice(ro, o_new, (start,))
    return rs, ro


@functools.partial(jax.jit, donate_argnums=(0,))
def _dev_scatter_w(w, idx, vals):
    """Edge-weight upsert: re-linking an existing (src, dst, type) edge
    updates its weight lane in place (pow2-padded idempotent scatter)."""
    return w.at[idx].set(vals)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _dev_compact_rows(rs, ro, gather, n_new):
    """Repack the row-incidence lanes through a compaction's old->new map:
    new row r takes old row gather[r]; the tail clears to -1.  Donated
    in-place gather, sticky capacity — the expansion executable survives."""
    live = jnp.arange(rs.shape[0]) < n_new
    return (jnp.where(live, rs[gather], -1),
            jnp.where(live, ro[gather], -1))


@functools.partial(jax.jit,
                   static_argnames=("hops", "k", "seed_k", "decay"))
def _expand_device(edge_src, edge_dst, edge_type, edge_w, node_ns,
                   row_sub, row_obj, row_labels, rankings, q_ns, type_w,
                   hops_b, n_edges, n_rows, *, hops: int, k: int,
                   seed_k: int, decay: float):
    """Batched k-hop expansion: ONE gather/scatter-max launch per hop over
    the full edge lanes, whole batch at once.  All counts are traced
    (`n_edges`, `n_rows`) and the executable is keyed only on the pow2 lane
    capacities and the (hops, k, seed_k) bucket — appends within a capacity
    bucket reuse it.  Returns (row ids (B, kk) i32 best-first -1-padded,
    scores (B, kk) f32).  Float32 op order here is the oracle contract — see
    kernels/ref.graph_expand_ref, which mirrors it expression by
    expression."""
    B = q_ns.shape[0]
    Ncap = node_ns.shape[0]
    Ecap = edge_src.shape[0]
    Rcap = row_sub.shape[0]
    Lcap = row_labels.shape[0]
    decay32 = jnp.float32(decay)
    bidx = jnp.arange(B)[:, None]
    # -- seeds: top seed_k of every upstream ranking -> incident nodes ------
    seeds = jnp.concatenate(
        [r[:, : min(seed_k, r.shape[1])] for r in rankings], axis=1)
    ok = (seeds >= 0) & (seeds < n_rows)
    srow = jnp.where(ok, seeds, 0)
    ok = ok & (row_labels[jnp.clip(srow, 0, Lcap - 1)] == q_ns[:, None])
    sub = jnp.where(ok, row_sub[jnp.clip(srow, 0, Rcap - 1)], -1)
    obj = jnp.where(ok, row_obj[jnp.clip(srow, 0, Rcap - 1)], -1)
    F = jnp.zeros((B, Ncap), jnp.float32)
    for nodes in (sub, obj):
        F = F.at[bidx, jnp.clip(nodes, 0, Ncap - 1)].max(
            jnp.where(nodes >= 0, jnp.float32(1.0), jnp.float32(0.0)))
    ns_ok = node_ns[None, :] == q_ns[:, None]            # (B, Ncap)
    F = jnp.where(ns_ok, F, 0.0)
    # Seed nodes deliberately never score rows — not their hop-0 activation
    # and not any hop>=1 re-activation (a hub seed like a speaker's name
    # round-trips back at full strength and would tie every row it touches,
    # crowding the actual discoveries out of the top-k).  The expanded
    # ranking is rows reached through NEWLY discovered nodes only; the seed
    # rows themselves are the upstream rankings' job.
    seed_mask = F > 0
    acc = jnp.zeros_like(F)
    # -- static per-expansion edge terms ------------------------------------
    e_ok = jnp.arange(Ecap) < n_edges
    src_c = jnp.clip(edge_src, 0, Ncap - 1)
    dst_c = jnp.clip(edge_dst, 0, Ncap - 1)
    deg = jnp.zeros((Ncap,), jnp.int32).at[src_c].add(
        jnp.where(e_ok, 1, 0))
    deg_f = jnp.maximum(deg, 1).astype(jnp.float32)
    we = type_w[:, jnp.clip(edge_type, 0, N_EDGE_TYPES - 1)] \
        * edge_w[None, :]                                 # (B, Ecap)
    for h in range(1, hops + 1):
        c = F[:, src_c] * we          # float32 op order = oracle contract
        c = c * decay32
        c = c / deg_f[src_c][None, :]
        c = jnp.where(e_ok[None, :], c, 0.0)
        newF = jnp.zeros((B, Ncap), jnp.float32).at[bidx, dst_c[None, :]
                                                   ].max(c)
        newF = jnp.where(ns_ok, newF, 0.0)
        live = (hops_b >= h)[:, None]
        newF = jnp.where(live, newF, 0.0)
        acc = jnp.maximum(acc, newF)
        F = newF
    # -- node activations -> row ranking ------------------------------------
    acc = jnp.where(seed_mask, 0.0, acc)
    r_idx = jnp.arange(Rcap, dtype=jnp.int32)
    rl = row_labels[jnp.clip(r_idx, 0, Lcap - 1)]
    r_ok = (r_idx[None, :] < n_rows) & (rl[None, :] == q_ns[:, None])
    rs = jnp.where(row_sub[None, :] >= 0,
                   acc[:, jnp.clip(row_sub, 0, Ncap - 1)], 0.0)
    ro = jnp.where(row_obj[None, :] >= 0,
                   acc[:, jnp.clip(row_obj, 0, Ncap - 1)], 0.0)
    score = jnp.where(r_ok, jnp.maximum(rs, ro), 0.0)    # (B, Rcap)
    hit = score > 0
    neg = jnp.where(hit, -score, jnp.inf)
    sid = jnp.where(hit, r_idx[None, :], jnp.iinfo(jnp.int32).max)
    out = jnp.where(hit, r_idx[None, :], -1)
    # lexicographic (-score, row id): descending score, ties to lower row
    neg_s, _, ids_s = jax.lax.sort((neg, sid, out), dimension=1,
                                   num_keys=2, is_stable=True)
    kk = min(k, Rcap)
    alive = neg_s[:, :kk] < jnp.inf
    return (jnp.where(alive, ids_s[:, :kk], -1),
            jnp.where(alive, -neg_s[:, :kk], 0.0))


class GraphInvariantError(RuntimeError):
    """A graph-internal alignment invariant was violated (lane drift).
    The store wraps this into StoreInvariantError at its boundary."""


class MemoryGraph:
    """Entity/temporal/causal graph with host-mirror truth and in-place
    device lanes.  All writes land host-side immediately; `sync_device()`
    pushes the accumulated delta to the device lanes in one pow2-padded
    donated append per lane family (the store calls it once per flush)."""

    def __init__(self):
        # host truth: nodes
        self._node_text: List[str] = []
        self._node_ns = np.full((64,), -1, np.int32)
        self._intern: Dict[Tuple[int, str], int] = {}
        # host truth: edges (directed COO lanes; CSR offsets are derived on
        # demand by the oracle/tests — the device expansion relaxes the COO
        # lanes directly, which is what keeps appends O(delta))
        self._edge_src = np.zeros((64,), np.int32)
        self._edge_dst = np.zeros((64,), np.int32)
        self._edge_type = np.zeros((64,), np.int32)
        self._edge_w = np.zeros((64,), np.float32)
        self._n_edges = 0
        self._edge_idx: Dict[Tuple[int, int, int], int] = {}
        # host truth: row incidence
        self._row_sub = np.full((64,), -1, np.int32)
        self._row_obj = np.full((64,), -1, np.int32)
        self._n_rows = 0
        # per-(ns, triple-key) version-chain tail: last object node
        self._tail: Dict[Tuple[int, str], int] = {}
        # device lanes (lazily materialized, then updated in place)
        self._dev = None                     # dict of jnp lanes
        self._synced = (0, 0, 0)             # (nodes, edges, rows) on device
        self._pending_w: List[int] = []      # edge ids with re-set weights
        self.counters = {"expansions": 0, "edges_upserted": 0}

    # -- sizes --------------------------------------------------------------
    @property
    def n_nodes(self) -> int:
        return len(self._node_text)

    @property
    def n_edges(self) -> int:
        return self._n_edges

    @property
    def n_rows(self) -> int:
        return self._n_rows

    def edge_type_counts(self) -> Dict[str, int]:
        et = self._edge_type[: self._n_edges]
        return {name: int((et == i).sum())
                for i, name in enumerate(EDGE_TYPE_NAMES)}

    # -- host mirrors (oracle / snapshot readers) ---------------------------
    def node_ns(self) -> np.ndarray:
        return self._node_ns[: self.n_nodes].copy()

    def edges(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        m = self._n_edges
        return (self._edge_src[:m].copy(), self._edge_dst[:m].copy(),
                self._edge_type[:m].copy(), self._edge_w[:m].copy())

    def row_incidence(self) -> Tuple[np.ndarray, np.ndarray]:
        return (self._row_sub[: self._n_rows].copy(),
                self._row_obj[: self._n_rows].copy())

    def csr_offsets(self) -> np.ndarray:
        """(n_nodes + 1,) int64 CSR row offsets of the out-adjacency,
        derived from the COO lanes (docs/STORAGE.md documents the layout;
        tests cross-check the device degree normalization against it)."""
        counts = np.bincount(self._edge_src[: self._n_edges],
                             minlength=self.n_nodes)
        return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)

    # -- writes (host first, device delta on sync) --------------------------
    def intern(self, ns_id: int, text: str) -> int:
        """Create-or-get the node for (namespace, normalized entity)."""
        key = (int(ns_id), normalize_entity(text))
        node = self._intern.get(key)
        if node is not None:
            return node
        node = self.n_nodes
        if node >= self._node_ns.shape[0]:
            cap = _next_capacity(node + 1, floor=2 * self._node_ns.shape[0])
            grown = np.full((cap,), -1, np.int32)
            grown[:node] = self._node_ns[:node]
            self._node_ns = grown
            self._invalidate_device()
        self._node_text.append(key[1])
        self._node_ns[node] = key[0]
        self._intern[key] = node
        return node

    def _grow_edges(self, need: int) -> None:
        cap = self._edge_src.shape[0]
        if need <= cap:
            return
        cap = _next_capacity(need, floor=2 * cap)
        for name in ("_edge_src", "_edge_dst", "_edge_type"):
            grown = np.zeros((cap,), np.int32)
            grown[: self._n_edges] = getattr(self, name)[: self._n_edges]
            setattr(self, name, grown)
        w = np.zeros((cap,), np.float32)
        w[: self._n_edges] = self._edge_w[: self._n_edges]
        self._edge_w = w
        self._invalidate_device()

    def add_edge(self, src: int, dst: int, etype: int,
                 weight: float = 1.0) -> None:
        """Upsert ONE directed edge.  A new (src, dst, type) appends; an
        existing one keeps its lane slot and re-sets its weight (the device
        weight lane is patched by the next sync)."""
        if src == dst:
            return
        key = (int(src), int(dst), int(etype))
        eid = self._edge_idx.get(key)
        w32 = np.float32(weight)
        if eid is not None:
            if self._edge_w[eid] != w32:
                self._edge_w[eid] = w32
                self._pending_w.append(eid)
            return
        self._grow_edges(self._n_edges + 1)
        eid = self._n_edges
        self._edge_src[eid], self._edge_dst[eid] = key[0], key[1]
        self._edge_type[eid], self._edge_w[eid] = key[2], w32
        self._edge_idx[key] = eid
        self._n_edges += 1
        self.counters["edges_upserted"] += 1

    def link_nodes(self, src: int, dst: int, etype: int,
                   weight: float = 1.0) -> None:
        """Symmetric upsert: both directions (the expansion is directed)."""
        self.add_edge(src, dst, etype, weight)
        self.add_edge(dst, src, etype, weight)

    def append_row(self, row: int, sub_node: int, obj_node: int) -> None:
        """Record row `row`'s incidence.  Rows MUST arrive in global-row
        order — the lane position IS the row id (the store's alignment
        invariant; drift raises GraphInvariantError)."""
        if row != self._n_rows:
            raise GraphInvariantError(
                f"row-incidence drift: appending row {row}, lane holds "
                f"{self._n_rows}")
        cap = self._row_sub.shape[0]
        if row >= cap:
            cap = _next_capacity(row + 1, floor=2 * cap)
            for name in ("_row_sub", "_row_obj"):
                grown = np.full((cap,), -1, np.int32)
                grown[: self._n_rows] = getattr(self, name)[: self._n_rows]
                setattr(self, name, grown)
            self._invalidate_device()
        self._row_sub[row] = int(sub_node)
        self._row_obj[row] = int(obj_node)
        self._n_rows += 1

    def ingest_session(self, ns_id: int, triples: Sequence,
                       rows: Sequence[int]) -> None:
        """Ingest one flushed session's triples (with their freshly
        assigned global rows, in order): intern entities, append row
        incidence, and upsert the three edge families.  Deterministic given
        prior graph state — WAL replay of the same flush records rebuilds
        the graph bit-identically (asserted in tests)."""
        prev_obj = None
        for tr, row in zip(triples, rows):
            sub = self.intern(ns_id, tr.subject)
            obj = self.intern(ns_id, tr.object)
            self.append_row(int(row), sub, obj)
            self.link_nodes(sub, obj, EDGE_ENTITY)
            if prev_obj is not None:
                self.link_nodes(prev_obj, obj, EDGE_TEMPORAL)
            prev_obj = obj
            tail_key = (int(ns_id), tr.key())
            last = self._tail.get(tail_key)
            if last is not None and last != obj:
                self.link_nodes(last, obj, EDGE_CAUSAL)
            self._tail[tail_key] = obj

    # -- device residency ---------------------------------------------------
    def _invalidate_device(self) -> None:
        self._dev = None

    def _ensure_device(self) -> None:
        """Materialize the device lanes from the host mirror — first
        expansion and after capacity changes only, never steady-state."""
        if self._dev is not None:
            return
        self._dev = {
            "node_ns": jnp.asarray(self._node_ns),
            "edge_src": jnp.asarray(self._edge_src),
            "edge_dst": jnp.asarray(self._edge_dst),
            "edge_type": jnp.asarray(self._edge_type),
            "edge_w": jnp.asarray(self._edge_w),
            "row_sub": jnp.asarray(self._row_sub),
            "row_obj": jnp.asarray(self._row_obj),
        }
        self._synced = (self.n_nodes, self._n_edges, self._n_rows)
        self._pending_w = []

    def sync_device(self) -> None:
        """Push the host-side delta since the last sync to the device lanes
        in place: one pow2-padded donated append per lane family plus one
        weight scatter when upserts re-weighted existing edges.  A no-op
        until the first expansion materializes the lanes."""
        if self._dev is None:
            return
        d = self._dev
        sn, se, sr = self._synced
        if self.n_nodes > sn:
            m = self.n_nodes - sn
            pad = max(m, min(_next_pow2(m), self._node_ns.shape[0] - sn))
            up = np.full((pad,), -1, np.int32)
            up[:m] = self._node_ns[sn: sn + m]
            d["node_ns"] = _dev_append_nodes(d["node_ns"], jnp.asarray(up),
                                             jnp.int32(sn))
        if self._n_edges > se:
            m = self._n_edges - se
            pad = max(m, min(_next_pow2(m), self._edge_src.shape[0] - se))
            ups = []
            for lane, fill, dt in ((self._edge_src, 0, np.int32),
                                   (self._edge_dst, 0, np.int32),
                                   (self._edge_type, 0, np.int32),
                                   (self._edge_w, 0.0, np.float32)):
                up = np.full((pad,), fill, dt)
                up[:m] = lane[se: se + m]
                ups.append(jnp.asarray(up))
            d["edge_src"], d["edge_dst"], d["edge_type"], d["edge_w"] = \
                _dev_append_edges(d["edge_src"], d["edge_dst"],
                                  d["edge_type"], d["edge_w"], *ups,
                                  jnp.int32(se))
        if self._n_rows > sr:
            m = self._n_rows - sr
            pad = max(m, min(_next_pow2(m), self._row_sub.shape[0] - sr))
            up_s = np.full((pad,), -1, np.int32)
            up_o = np.full((pad,), -1, np.int32)
            up_s[:m] = self._row_sub[sr: sr + m]
            up_o[:m] = self._row_obj[sr: sr + m]
            d["row_sub"], d["row_obj"] = _dev_append_rows(
                d["row_sub"], d["row_obj"], jnp.asarray(up_s),
                jnp.asarray(up_o), jnp.int32(sr))
        if self._pending_w:
            # only already-synced edges need the patch (fresh appends above
            # carried their final weight)
            idx = sorted({e for e in self._pending_w if e < se})
            if idx:
                pad = _next_pow2(len(idx))
                idx_up = np.asarray(
                    idx + [idx[-1]] * (pad - len(idx)), np.int32)
                d["edge_w"] = _dev_scatter_w(
                    d["edge_w"], jnp.asarray(idx_up),
                    jnp.asarray(self._edge_w[idx_up]))
        self._synced = (self.n_nodes, self._n_edges, self._n_rows)
        self._pending_w = []

    # -- the read path ------------------------------------------------------
    def expand(self, rankings: Sequence, q_ns, row_labels, type_w, hops_b,
               *, k: int, max_hops: int, seed_k: int = 8,
               decay: float = 0.5):
        """Batched expansion over the device lanes.  `rankings` are the
        upstream (B, P_i) device id matrices (dense/sparse, -1-padded,
        best-first); their first `seed_k` columns seed the frontier.
        `row_labels` is the bank's cached (capacity,) effective-label
        device buffer (tombstones/demoted rows -1 — they neither seed nor
        surface).  `type_w` (B, 3) f32 per-request edge-type weights,
        `hops_b` (B,) i32 per-request hop counts (0 = seeds only).
        `max_hops` is the static unrolled depth (pow2-bucketed by the
        caller); `k` the ranking width.  Returns (ids (B, k) i32, scores
        (B, k) f32), both on the device: nothing is read to the host."""
        self._ensure_device()
        self.sync_device()
        d = self._dev
        hops = max(1, int(max_hops))
        ids, scores = _expand_device(
            d["edge_src"], d["edge_dst"], d["edge_type"], d["edge_w"],
            d["node_ns"], d["row_sub"], d["row_obj"], row_labels,
            tuple(jnp.asarray(r, jnp.int32) for r in rankings),
            jnp.asarray(q_ns, jnp.int32),
            jnp.asarray(type_w, jnp.float32),
            jnp.asarray(hops_b, jnp.int32),
            jnp.int32(self._n_edges), jnp.int32(self._n_rows),
            hops=hops, k=int(k), seed_k=int(seed_k), decay=float(decay))
        self.counters["expansions"] += 1
        if ids.shape[1] < k:
            ids = jnp.pad(ids, ((0, 0), (0, k - ids.shape[1])),
                          constant_values=-1)
            scores = jnp.pad(scores, ((0, 0), (0, k - scores.shape[1])))
        return ids, scores

    # -- compaction / persistence -------------------------------------------
    def compact_rows(self, old_to_new: np.ndarray) -> None:
        """Remap the row-incidence lanes through a store compaction's
        old->new row map ((n_old,) with -1 for dropped rows).  Kept rows
        keep their incidence; dropped rows' incidences vanish with them.
        Sticky capacity; the device lanes repack via a donated gather."""
        old_to_new = np.asarray(old_to_new, np.int64)
        n_old = old_to_new.shape[0]
        if n_old != self._n_rows:
            raise GraphInvariantError(
                f"compaction drift: map covers {n_old} rows, lanes hold "
                f"{self._n_rows}")
        keep = np.where(old_to_new >= 0)[0]
        n_new = int(keep.size)
        cap = self._row_sub.shape[0]
        new_sub = np.full((cap,), -1, np.int32)
        new_obj = np.full((cap,), -1, np.int32)
        new_sub[:n_new] = self._row_sub[keep]
        new_obj[:n_new] = self._row_obj[keep]
        self._row_sub, self._row_obj = new_sub, new_obj
        self._n_rows = n_new
        if self._dev is not None:
            gather = np.zeros((cap,), np.int32)
            gather[:n_new] = keep
            self._dev["row_sub"], self._dev["row_obj"] = _dev_compact_rows(
                self._dev["row_sub"], self._dev["row_obj"],
                jnp.asarray(gather), jnp.int32(n_new))
            self._synced = (self._synced[0], self._synced[1], n_new)

    def snapshot_arrays(self) -> Dict[str, np.ndarray]:
        """Numeric lanes for checkpoint/io.py (tight, not capacity-padded)."""
        m, r = self._n_edges, self._n_rows
        return {
            "graph_node_ns": self._node_ns[: self.n_nodes].copy(),
            "graph_edge_src": self._edge_src[:m].copy(),
            "graph_edge_dst": self._edge_dst[:m].copy(),
            "graph_edge_type": self._edge_type[:m].copy(),
            "graph_edge_w": self._edge_w[:m].copy(),
            "graph_row_sub": self._row_sub[:r].copy(),
            "graph_row_obj": self._row_obj[:r].copy(),
        }

    def snapshot_meta(self) -> dict:
        """Non-numeric state: node texts (interning rebuilds from them) and
        the version-chain tails (so post-restore ingest keeps extending the
        same causal chains the writer would have)."""
        return {
            "nodes": list(self._node_text),
            "tail": [[int(ns), key, int(node)]
                     for (ns, key), node in sorted(self._tail.items())],
        }

    @classmethod
    def from_snapshot(cls, arrays: Dict[str, np.ndarray],
                      meta: dict) -> "MemoryGraph":
        g = cls()
        node_ns = np.asarray(arrays["graph_node_ns"], np.int32)
        texts = [str(t) for t in meta["nodes"]]
        if len(texts) != node_ns.shape[0]:
            raise GraphInvariantError(
                f"restore: {len(texts)} node texts vs "
                f"{node_ns.shape[0]} node labels")
        g._node_ns = np.full((_next_capacity(len(texts)),), -1, np.int32)
        g._node_ns[: len(texts)] = node_ns
        g._node_text = texts
        g._intern = {(int(ns), t): i
                     for i, (ns, t) in enumerate(zip(node_ns, texts))}
        src = np.asarray(arrays["graph_edge_src"], np.int32)
        m = src.shape[0]
        ecap = _next_capacity(m)
        g._edge_src = np.zeros((ecap,), np.int32)
        g._edge_dst = np.zeros((ecap,), np.int32)
        g._edge_type = np.zeros((ecap,), np.int32)
        g._edge_w = np.zeros((ecap,), np.float32)
        g._edge_src[:m] = src
        g._edge_dst[:m] = np.asarray(arrays["graph_edge_dst"], np.int32)
        g._edge_type[:m] = np.asarray(arrays["graph_edge_type"], np.int32)
        g._edge_w[:m] = np.asarray(arrays["graph_edge_w"], np.float32)
        g._n_edges = m
        g._edge_idx = {(int(g._edge_src[i]), int(g._edge_dst[i]),
                        int(g._edge_type[i])): i for i in range(m)}
        sub = np.asarray(arrays["graph_row_sub"], np.int32)
        r = sub.shape[0]
        rcap = _next_capacity(r)
        g._row_sub = np.full((rcap,), -1, np.int32)
        g._row_obj = np.full((rcap,), -1, np.int32)
        g._row_sub[:r] = sub
        g._row_obj[:r] = np.asarray(arrays["graph_row_obj"], np.int32)
        g._n_rows = r
        g._tail = {(int(ns), str(key)): int(node)
                   for ns, key, node in meta.get("tail", [])}
        return g

    def stats(self) -> dict:
        """Durable-state gauges only (snapshot-identical across restore —
        session-local counters like expansion counts live in telemetry)."""
        return {
            "nodes": self.n_nodes,
            "edges": self._n_edges,
            "rows_with_incidence": int(
                (self._row_sub[: self._n_rows] >= 0).sum()),
            **{f"edges_{n}": c for n, c in self.edge_type_counts().items()},
        }
