"""MemoryService — the multi-tenant memory layer (ROADMAP north-star).

MemoriMemory is single-tenant: one object, one bank, one kernel launch per
query.  A production deployment serves millions of (user, conversation)
namespaces, and the amortization that makes that affordable on TPU is
*batching*: pending queries across tenants are embedded in ONE
`embed_texts` call and scored in ONE namespace-masked `topk_mips` launch
against a packed multi-tenant bank (per-row namespace ids; cross-namespace
hits masked to NEG_INF before the top-k merge — kernels/topk_mips.py), the
sparse side is ONE stacked (B, N) BM25 scoring op with per-query namespace
masks, and the dense/sparse rankings fuse in ONE on-device
`rrf_fuse_batch` (core/hybrid.py).  The bank, its alive/namespace labels
and the row-count all live device-resident (core/vector_index.py): a
steady-state `retrieve_batch` issues zero bank H2D transfers and zero
recompiles while the bank grows within a power-of-two capacity bucket.
Writes amortize the same way: `enqueue()` queues sessions for free and
`flush()` ingests everything pending across all tenants through one
`embed_texts` call and one in-place device bank append (`record()` is the
synchronous enqueue-then-flush).

Storage — the packed bank, the BM25 corpus, the per-tenant triple/summary
stores and the row↔namespace↔triple mapping — lives in `core/store.py`'s
MemoryStore, which also provides `compact()` (tombstone reclamation with
row-id remapping) and `snapshot()` / `MemoryService.restore()` persistence.
Everything that happens *between* requests — WAL-backed incremental
persistence, the time-based background flusher with backpressure,
auto-compaction and snapshot rotation — lives in `core/lifecycle.py`'s
LifecycleRuntime; pass `policy=`/`data_dir=` to mount one (or
`MemoryService.recover(data_dir, ...)` to come back after a crash), and the
service routes writes, maintenance and the read path through its lock.

Public-facing batch sizes are ragged, so `retrieve_batch` pads every batch
to the next power-of-two Q bucket (padded queries carry a never-assigned
namespace id and match nothing): the whole read path — masked `topk_mips`,
stacked BM25, on-device RRF — sees only bucketed shapes, bounding the
executable count regardless of traffic shape.

Isolation invariants:
  * a triple recorded under namespace A can never surface for namespace B
    (dense path: kernel mask; sparse path: BM25 per-namespace scoping);
  * `retrieve_batch([(ns, q), ...])` returns results identical to the same
    retrieves issued sequentially (asserted in tests/test_service.py);
  * tombstoned rows (evict / evict_superseded) never surface again, and
    their vectors are physically zeroed (compact() then reclaims them).

The public surface is typed (core/api.py): `retrieve_batch` takes
`RetrieveRequest`s (tuples still accepted) and runs them through an
explicit `RetrievalPlan` — embed → dense → sparse → fuse → budget, with
dense-only / sparse-only / raw (no-budget) variants — in `execute()`, the
engine behind every read.  Per-request `top_k`, dense/sparse weights and
stage sets are honored inside the shared launches (fusion at max(k) +
per-row slicing, a (B, R) weight matrix, -1-masked rankings).  Mount a
`MemoryScheduler` (`start_scheduler()`, core/scheduler.py) and the sync
wrappers coalesce concurrent clients' single requests into one batched
launch per tick — continuous batching for memory ops.

`namespace(name)` returns a MemoriMemory-compatible view, so MemoriClient
and the serving launchers run against the service unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.common.utils import next_pow2
from repro.core.admission import AdmissionError
from repro.core.api import (RawRetrieval, RetrievalPlan, RetrieveRequest,
                            as_retrieve_request)
from repro.core.budget import TokenBudgeter
from repro.core.extraction import Extractor, Message
from repro.core.hybrid import rrf_fuse_batch
from repro.core.lifecycle import LifecyclePolicy, LifecycleRuntime
from repro.core.memory import ANSWER_PROMPT, MemoriMemory, RetrievedContext
from repro.core.store import MemoryStore
from repro.core.summaries import Summary
from repro.core.triples import Triple
from repro.data.tokenizer import HashTokenizer
from repro.obs.telemetry import (RECORD_LATENCY, RETRIEVE_LATENCY,
                                 get_telemetry)

# graph-stage fallbacks when neither the request nor the plan sets them:
# 2 hops reaches friend-of-a-fact chains, causal/temporal edges slightly
# discounted against direct co-occurrence, and the expanded ranking fuses
# below the dense column's weight (it corroborates, it does not dominate)
_GRAPH_HOPS = 2
_GRAPH_EDGE_WEIGHTS = (1.0, 0.9, 0.9)
_GRAPH_WEIGHT = 0.6


@dataclasses.dataclass(frozen=True)
class _Resolved:
    """One request's options after plan/service defaults are folded in."""
    k: int
    dense_weight: float
    sparse_weight: float
    dense: bool
    sparse: bool
    graph: bool
    budget: bool
    hops: int = _GRAPH_HOPS
    edge_weights: Tuple[float, float, float] = _GRAPH_EDGE_WEIGHTS
    graph_weight: float = _GRAPH_WEIGHT


class MemoryService:
    def __init__(self, embedder=None, extractor: Optional[Extractor] = None,
                 dim: int = 256, budget: int = 1300, top_k: int = 10,
                 tokenizer: HashTokenizer | None = None,
                 use_kernel: bool = True,
                 dense_weight: float = 1.0, sparse_weight: float = 0.7,
                 pool: int = 64, flush_every: Optional[int] = None,
                 store: Optional[MemoryStore] = None,
                 policy: Optional[LifecyclePolicy] = None,
                 data_dir: Optional[str] = None,
                 runtime: Optional[LifecycleRuntime] = None,
                 plan: Optional[RetrievalPlan] = None,
                 quantize: str = "none", rescore: int = 4,
                 shards: int = 1, mesh=None):
        if store is None and runtime is not None:
            store = runtime.store
        if store is None:
            if embedder is None:
                raise ValueError("MemoryService needs an embedder or a store")
            store = MemoryStore(embedder, extractor, dim=dim,
                                use_kernel=use_kernel, tokenizer=tokenizer,
                                quantize=quantize, rescore=rescore,
                                shards=shards, mesh=mesh)
        self.store = store
        self.embedder = store.embedder
        self.extractor = store.extractor
        self.tokenizer = store.tokenizer
        self.budgeter = TokenBudgeter(budget=budget, tokenizer=self.tokenizer)
        self.top_k = top_k
        self.dense_weight = dense_weight
        self.sparse_weight = sparse_weight
        self.pool = pool
        self.flush_every = flush_every
        self.plan = plan or RetrievalPlan()
        # a mounted MemoryScheduler (core/scheduler.py) re-routes the sync
        # read wrappers through its cross-client micro-batching ticks
        self.scheduler = None
        if runtime is not None:
            if runtime.store is not self.store:
                raise ValueError("runtime is mounted on a different store")
        elif policy is not None or data_dir is not None:
            runtime = LifecycleRuntime(self.store, data_dir=data_dir,
                                       policy=policy)
        self.runtime = runtime

    def _guard(self):
        """The runtime's lock when one is mounted (serializes requests
        against background flush/compaction/rotation), else a no-op."""
        return self.runtime.lock if self.runtime else contextlib.nullcontext()

    # the underlying indices, exposed for tests/benchmarks and the SDK
    @property
    def vindex(self):
        return self.store.vindex

    @property
    def bm25(self):
        return self.store.bm25

    # -- persistence -------------------------------------------------------
    @classmethod
    def restore(cls, path: str, embedder,
                extractor: Optional[Extractor] = None,
                use_kernel: bool = True,
                tokenizer: HashTokenizer | None = None,
                **service_kwargs) -> "MemoryService":
        """Rebuild a service from `snapshot(path)`: the restored service
        answers `retrieve_batch` identically to the one that wrote it.
        `quantize=`/`rescore=` in service_kwargs pick the restored
        index's device residency mode (snapshots are always f32)."""
        store = MemoryStore.restore(
            path, embedder, extractor=extractor, use_kernel=use_kernel,
            tokenizer=tokenizer,
            quantize=service_kwargs.pop("quantize", "none"),
            rescore=service_kwargs.pop("rescore", 4),
            shards=service_kwargs.pop("shards", 1),
            mesh=service_kwargs.pop("mesh", None))
        return cls(store=store, **service_kwargs)

    @classmethod
    def recover(cls, data_dir: str, embedder,
                extractor: Optional[Extractor] = None,
                policy: Optional[LifecyclePolicy] = None,
                use_kernel: bool = True, dim: int = 256,
                tokenizer: HashTokenizer | None = None,
                shards: Optional[int] = None, mesh=None,
                **service_kwargs) -> "MemoryService":
        """Rebuild a service from a lifecycle runtime's durable directory:
        newest restorable snapshot + ordered WAL replay.  The recovered
        service answers `retrieve_batch` bit-identically to the pre-crash
        one up to the last durable flush, and keeps journaling to the same
        directory.  `dim` matters only when the directory holds no
        snapshot yet (the fresh replay store must match the embedder).
        `shards=None` autodetects the sharded WAL layout on disk."""
        rt = LifecycleRuntime.recover(data_dir, embedder,
                                      extractor=extractor, policy=policy,
                                      use_kernel=use_kernel, dim=dim,
                                      tokenizer=tokenizer, shards=shards,
                                      mesh=mesh)
        return cls(runtime=rt, **service_kwargs)

    def snapshot(self, path: str) -> int:
        """Flush pending writes, then persist the whole store to an
        explicit path (manual escape hatch — a mounted runtime's rotation
        is `rotate()`).  Returns bytes written."""
        with self._guard():
            return self.store.snapshot(path)

    def rotate(self) -> dict:
        """Snapshot rotation through the mounted runtime: full snapshot,
        retention pruning, WAL truncation."""
        if self.runtime is None:
            raise RuntimeError("rotate() needs a mounted LifecycleRuntime")
        return self.runtime.rotate()

    def close(self, *, final_snapshot: bool = True) -> None:
        """Stop the mounted scheduler (drains queued requests) and the
        background runtime (final flush + snapshot when durable).  Safe to
        call on a scheduler-less / runtime-less service.  Idempotent."""
        if self.scheduler is not None:
            self.scheduler.close()
        if self.runtime is not None:
            self.runtime.close(final_snapshot=final_snapshot)

    def start_scheduler(self, **kwargs):
        """Mount a MemoryScheduler: from here on the sync read wrappers
        (`retrieve`, `retrieve_batch`) coalesce with every other client's
        concurrent requests into one device launch per tick.  Returns the
        scheduler (also available as `self.scheduler`; the constructor
        refuses to mount over a live one)."""
        from repro.core.scheduler import MemoryScheduler
        return MemoryScheduler(self, **kwargs)

    def __enter__(self) -> "MemoryService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- tenancy -----------------------------------------------------------
    def namespaces(self) -> List[str]:
        with self._guard():
            return self.store.namespaces()

    def namespace(self, name: str) -> "NamespaceView":
        return NamespaceView(self, name)

    # -- write path ----------------------------------------------------------
    def record(self, namespace: str, session_id: str,
               messages: Sequence[Message]) -> Tuple[List[Triple], Summary]:
        """Synchronous ingest of one session: enqueue + flush (one write
        path — anything else pending is drained in the same batch)."""
        t0 = time.perf_counter()
        with self._guard():
            if self.runtime is not None:
                if self.runtime.closed:
                    raise RuntimeError(
                        "service is closed: writes would bypass the "
                        "journal (recover/remount before writing again)")
                self.runtime.note_activity()
            out = self.store.ingest(namespace, session_id, messages)
        get_telemetry().observe(
            RECORD_LATENCY, time.perf_counter() - t0,
            help="synchronous record (enqueue + flush) latency")
        return out

    def enqueue(self, namespace: str, session_id: str,
                messages: Sequence[Message],
                conversation_id: Optional[str] = None) -> None:
        """Async ingest: queue the session for the next `flush()`.  No
        extraction or embedding happens here.  With a mounted runtime the
        queue is bounded and backpressured per policy (the background
        flusher drains it); `flush_every` additionally triggers a
        count-based flush."""
        if self.runtime is not None:
            self.runtime.enqueue(namespace, session_id, messages,
                                 conversation_id=conversation_id)
        else:
            self.store.enqueue(namespace, session_id, messages,
                               conversation_id=conversation_id)
        if self.flush_every and self.store.pending_count >= self.flush_every:
            self.flush()

    def flush(self) -> int:
        """Drain all pending sessions (all tenants) through one embed call
        and one bank append.  Returns the number of sessions ingested."""
        if self.runtime is not None:
            return self.runtime.flush()
        return len(self.store.flush())

    def compact(self) -> dict:
        """Reclaim tombstoned rows (see MemoryStore.compact)."""
        with self._guard():
            return self.store.compact()

    # -- read path -------------------------------------------------------------
    def retrieve(self, namespace: str, query: str,
                 top_k: Optional[int] = None, **options) -> RetrievedContext:
        """Single-tenant retrieve.  Extra keyword options (`dense_weight`,
        `sparse_weight`, `stages`) become per-request RetrieveRequest
        fields.  With a mounted scheduler this coalesces with every other
        client's concurrent request into one device launch."""
        req = RetrieveRequest(namespace=namespace, query=query, top_k=top_k,
                              **options)
        return self.retrieve_batch([req])[0]

    def retrieve_batch(self, requests: Sequence, top_k: Optional[int] = None,
                       plan: Optional[RetrievalPlan] = None) -> List[Any]:
        """Requests -> per-request payloads (RetrievedContext, or
        RawRetrieval for no-budget plans).  Each request is an
        (namespace, query) tuple or a `RetrieveRequest` carrying its own
        `top_k` / weights / stages; the legacy batch-global `top_k` kwarg
        is the per-request default (explicit per-request values win).

        With a mounted MemoryScheduler the batch is submitted to it, so it
        fuses with whatever other clients queued in the same tick;
        otherwise (or with an explicit `plan`) it executes directly.  Either
        way the results are identical to sequential retrieve() calls."""
        reqs = [as_retrieve_request(r, top_k) for r in requests]
        if not reqs:
            return []
        sched = self.scheduler
        if plan is None and sched is not None and sched.can_submit():
            try:
                futures = sched.submit_many(reqs)
            except AdmissionError:
                # a QoS rejection (rate limit / shed) must surface, not
                # sneak through the direct engine — falling back would let
                # every rate-limited caller bypass admission control
                raise
            except RuntimeError:
                # the scheduler closed between can_submit() and the
                # submission (service shutdown racing a reader) — the
                # direct engine still answers
                pass
            else:
                return [f.result().result() for f in futures]
        return self.execute(reqs, plan=plan)

    def execute(self, requests: Sequence[RetrieveRequest],
                plan: Optional[RetrievalPlan] = None) -> List[Any]:
        """The retrieval engine: run a batch of typed requests through the
        plan's stage pipeline in ONE set of device launches.

        The cross-tenant hot path: one embed_texts call for every pending
        query, one stable-shape masked topk_mips launch against the
        device-resident packed bank (cached row labels — no per-call bank
        upload, no label rebuild), one stacked BM25 scoring op for the
        sparse side, and ONE on-device `rrf_fuse_batch` that fuses every
        request at once; the (B, k) fused ranking crosses to the host in a
        single transfer.  Reads are read-your-writes: pending enqueued
        sessions are flushed first.  Per-request options are honored inside
        the shared launches: fusion runs at max(top_k) and each row is
        sliced to its own k; weights ride in as a (B, R) matrix; a request
        excluded from a stage has that ranking's ids masked to -1 (so a
        dense-only request in a mixed batch answers exactly like a
        dense-only batch).  Stages a WHOLE batch skips are never launched.

        Q-shape bucketing: the batch is padded to the next power-of-two
        size before it touches the device (padded queries carry a
        never-assigned namespace id, so they match no row on either side
        and fuse to all -1); a public endpoint serving ragged batch sizes
        therefore mints at most log2(max_B) executables per stage instead
        of one per distinct B."""
        if not requests:
            return []
        tel = get_telemetry()
        t_exec = time.perf_counter()
        plan = plan or self.plan
        reqs = list(requests)
        res = [self._resolve(r, plan) for r in reqs]
        # only the dense search consumes query vectors, so only the
        # requests whose stage set includes it get embedded (a sparse-only
        # batch never embeds at all; excluded rows ride as zero vectors —
        # their dense ranking is masked to -1 regardless).  The (possibly
        # slow, possibly remote) embed call stays OUTSIDE the runtime lock
        # so it never stalls the flusher or blocked enqueuers.
        dense_rows = [i for i, rr in enumerate(res) if rr.dense]
        with tel.span("plan.embed", batch=len(dense_rows), launches=1):
            qvecs = (self.embedder.embed_texts([reqs[i].query
                                                for i in dense_rows])
                     if dense_rows else None)
        with self._guard():
            if self.runtime is not None:
                self.runtime.note_activity()
            if self.store.pending_count:
                # through the runtime when mounted: the read-your-writes
                # drain counts as a flush and wakes blocked enqueuers
                self.flush()
            # reads never allocate tenant state: unknown namespaces stay
            # unknown (no leak from typo'd/adversarial queries, evict()
            # stays evicted)
            tenants = [self.store.get(r.namespace) for r in reqs]
            vindex = self.store.vindex
            tiers = self.store.tiers
            if tiers is not None:
                for t in tenants:
                    if t is not None:
                        tiers.note_retrieve(t.ns_id)
            # graceful degradation: a request whose owning placement shard
            # is down answers empty with degraded=True — BOTH its rankings
            # are masked below, so the surviving requests in the batch are
            # bit-identical to a batch that never contained it
            sharded = self.store.sharded
            if sharded is not None and sharded.down:
                downed = [t is not None
                          and sharded.shard_of(t.ns_id) in sharded.down
                          for t in tenants]
            else:
                downed = [False] * len(reqs)
            B = len(reqs)
            # fuse at the pow2 ceiling of the largest requested k: k is a
            # jit-static arg of the fusion, so bucketing it bounds the
            # executable count under mixed-k traffic (a scheduler tick's
            # max(k) is whatever clients happened to share it) exactly like
            # the Q-shape bucketing below; each row still slices to its own
            # k — the prefix of a wider fusion IS the narrower fusion
            k_fuse = next_pow2(max(r.k for r in res))
            if vindex.n:
                # unknown tenants get a never-assigned ns id (>= 0, so it
                # can't collide with the -1 tombstone label): they match no
                # bank row on the dense side and select no documents on the
                # sparse side.  Padded queries reuse the same id.
                unused = self.store.namespace_id_count()
                ns_ids = [t.ns_id if t else unused for t in tenants]
                Bp = next_pow2(B)
                ns_pad = ns_ids + [unused] * (Bp - B)
                q_ns = np.asarray(ns_pad, np.int32)
                rankings, weight_cols = [], []
                if dense_rows:
                    with tel.span("plan.dense", batch=Bp, pool=self.pool,
                                  launches=1,
                                  sharded=sharded is not None) as sp:
                        qv = np.asarray(qvecs, np.float32)
                        qmat = np.zeros((Bp, qv.shape[1]), np.float32)
                        qmat[dense_rows] = qv
                        if sharded is not None:
                            # shard-wise placement: one launch through the
                            # namespace-masked sharded_topk (local top-k per
                            # shard, gathered + re-ranked globally); ids come
                            # back already in global-row space
                            _, dense_ids = self.store.sharded_search(
                                qmat, q_ns, k=self.pool)
                        else:
                            _, dense_ids = vindex.search_batch(qmat, q_ns,
                                                               k=self.pool)
                        if tiers is not None:
                            # a demoted namespace's rows are absent from the
                            # device bank: answer those requests from the
                            # host-mirror masked search (exact, just not
                            # accelerated) and mark them for promotion — the
                            # next maintenance tick brings the rows back in
                            # one batched upload
                            fb = [i for i in dense_rows
                                  if tenants[i] is not None
                                  and tiers.is_demoted(tenants[i].ns_id)]
                            if fb:
                                sp.set(host_fallbacks=len(fb))
                                _, hi = vindex.search_host(
                                    qmat[fb], q_ns[fb], k=self.pool)
                                with tel.span("device.wait"):
                                    dense_ids = np.asarray(dense_ids).copy()
                                dense_ids[fb] = hi
                                for i in fb:
                                    tiers.note_host_fallback(tenants[i].ns_id)
                        dense_ids = self._mask_ranking(
                            dense_ids,
                            [r.dense and not d for r, d in zip(res, downed)],
                            Bp)
                    rankings.append(dense_ids)
                    weight_cols.append(
                        [r.dense_weight for r in res]
                        + [self.dense_weight] * (Bp - B))
                if any(r.sparse for r in res):
                    with tel.span("plan.sparse", batch=Bp, pool=self.pool,
                                  launches=1):
                        _, sparse_ids = self.store.bm25.topk_batch_dev(
                            [r.query for r in reqs] + [""] * (Bp - B),
                            k=self.pool, namespaces=ns_pad)
                        sparse_ids = self._mask_ranking(
                            sparse_ids,
                            [r.sparse and not d for r, d in zip(res, downed)],
                            Bp)
                    rankings.append(sparse_ids)
                    weight_cols.append(
                        [r.sparse_weight for r in res]
                        + [self.sparse_weight] * (Bp - B))
                # graph expansion: the dense/sparse rankings' top rows seed
                # a batched k-hop walk over the store's entity graph; the
                # expanded rows join the fusion as a third ranking with
                # their own weight column.  Requests that skip the stage
                # (or whose shard is down) get the expanded ranking masked
                # to -1 — their fusion is bit-identical to a graph-less
                # batch.  Hop depth is per-request (traced vector); the
                # unrolled depth compiles at the pow2 bucket of the batch
                # max, so mixed-hops traffic reuses one executable.
                graph_wants = [r.graph and not d
                               for r, d in zip(res, downed)]
                if any(graph_wants) and rankings:
                    g = self.store.graph
                    hops_list = [rr.hops if w else 0
                                 for rr, w in zip(res, graph_wants)]
                    hops_arr = np.zeros((Bp,), np.int32)
                    hops_arr[:B] = hops_list
                    tw = np.zeros((Bp, 3), np.float32)
                    tw[:B] = [rr.edge_weights for rr in res]
                    max_hops = next_pow2(max(1, max(hops_list)))
                    with tel.span("plan.graph", batch=Bp, pool=self.pool,
                                  hops_compiled=max_hops,
                                  launches=1) as sp:
                        graph_ids, _ = g.expand(
                            rankings, q_ns,
                            self.store.row_namespaces_device(), tw,
                            hops_arr, k=self.pool, max_hops=max_hops,
                            seed_k=plan.graph_seed_k,
                            decay=plan.graph_decay)
                        graph_ids = self._mask_ranking(
                            graph_ids, graph_wants, Bp)
                        sp.set(nodes=g.n_nodes, edges=g.n_edges)
                    rankings.append(graph_ids)
                    weight_cols.append(
                        [r.graph_weight for r in res] + [0.0] * (Bp - B))
                with tel.span("plan.fuse", batch=Bp, k=k_fuse,
                              rankings=len(rankings), launches=1):
                    fused_ids, fused_scores = rrf_fuse_batch(
                        rankings,
                        weights=np.stack(
                            [np.asarray(c, np.float32) for c in weight_cols],
                            axis=1),
                        k=k_fuse)
                # the first host read of the tick's device work: the
                # stages above only dispatched it
                with tel.span("device.wait"):
                    fused_ids = np.asarray(fused_ids)[:B]
                    fused_scores = np.asarray(fused_scores)[:B]
            else:
                fused_ids = np.full((B, k_fuse), -1, np.int32)
                fused_scores = np.zeros((B, k_fuse), np.float32)
            # result assembly stays under the guard: the fused global row
            # ids are only valid until the next compaction remaps them
            out: List[Any] = []
            with tel.span("plan.budget", batch=B):
                for r, (rr, t) in enumerate(zip(res, tenants)):
                    # per-request top_k: the fused ranking is sorted
                    # best-first, so its k_r prefix IS the k=k_r fusion of
                    # the same inputs
                    ids = fused_ids[r][: rr.k]
                    scs = fused_scores[r][: rr.k]
                    if t is None:
                        if rr.budget:
                            text = MemoriMemory.render([], [])
                            out.append(RetrievedContext(
                                [], [], text, self.tokenizer.count(text)))
                        else:
                            out.append(RawRetrieval([], [], []))
                        continue
                    if rr.budget:
                        scored = [(t.triples.get(self.store.row_tid(int(g))),
                                   float(s))
                                  for g, s in zip(ids, scs) if g >= 0]
                        ctx = self.budgeter.select(scored, t.summaries)
                        text = MemoriMemory.render(ctx.triples, ctx.summaries)
                        out.append(RetrievedContext(
                            ctx.triples, ctx.summaries, text,
                            self.tokenizer.count(text), degraded=downed[r]))
                    else:
                        rows = [int(g) for g in ids if g >= 0]
                        out.append(RawRetrieval(
                            rows, [self.store.row_tid(g) for g in rows],
                            [float(s) for g, s in zip(ids, scs) if g >= 0],
                            degraded=downed[r]))
            n_down = sum(downed)
            if n_down:
                tel.inc("memori_degraded_responses", n_down,
                        help="requests answered empty because their "
                             "placement shard was down")
                tel.event("degraded_response", count=n_down,
                          shards=sorted(sharded.down) if sharded else [])
            tel.observe(RETRIEVE_LATENCY, time.perf_counter() - t_exec,
                        n=B, help="end-to-end execute() latency per request")
            return out

    def _resolve(self, req: RetrieveRequest, plan: RetrievalPlan) -> _Resolved:
        """Fold request -> plan -> service option defaults."""
        stages = req.stages if req.stages is not None else plan.stages
        dw = (req.dense_weight if req.dense_weight is not None
              else plan.dense_weight if plan.dense_weight is not None
              else self.dense_weight)
        sw = (req.sparse_weight if req.sparse_weight is not None
              else plan.sparse_weight if plan.sparse_weight is not None
              else self.sparse_weight)
        ew = (req.edge_weights if req.edge_weights is not None
              else plan.edge_weights if plan.edge_weights is not None
              else _GRAPH_EDGE_WEIGHTS)
        gw = (req.graph_weight if req.graph_weight is not None
              else plan.graph_weight if plan.graph_weight is not None
              else _GRAPH_WEIGHT)
        return _Resolved(
            k=req.top_k or plan.top_k or self.top_k,
            dense_weight=float(dw), sparse_weight=float(sw),
            dense="dense" in stages, sparse="sparse" in stages,
            graph="graph" in stages,
            budget="budget" in stages,
            hops=int(req.hops or plan.hops or _GRAPH_HOPS),
            edge_weights=tuple(float(w) for w in ew),
            graph_weight=float(gw))

    @staticmethod
    def _mask_ranking(ids, wants: List[bool], Bp: int):
        """Drop a ranking for the requests that excluded its stage: their
        rows become all -1 (fusion padding), so a dense-only request inside
        a mixed batch fuses exactly like a dense-only batch.  The all-True
        common case is launch-free."""
        if all(wants):
            return ids
        mask = np.ones((Bp,), bool)
        mask[: len(wants)] = wants
        return jnp.where(jnp.asarray(mask)[:, None], ids, -1)

    def answer_prompt(self, namespace: str, question: str
                      ) -> Tuple[str, RetrievedContext]:
        ctx = self.retrieve(namespace, question)
        return ANSWER_PROMPT.format(memories=ctx.text,
                                    question=question), ctx

    # -- eviction ----------------------------------------------------------------
    def evict(self, namespace: str) -> int:
        """Drop a whole tenant: tombstone its bank rows + BM25 docs, free its
        stores.  Returns the number of rows evicted."""
        with self._guard():
            return self.store.evict_namespace(namespace)

    def evict_superseded(self, namespace: str) -> int:
        """Physically evict triples superseded under conflict resolution
        (triples.latest_for_key keeps the newest version of every
        (subject, predicate) key; the older versions leave the indices)."""
        with self._guard():
            return self.store.evict_superseded(namespace)

    # -- shard lifecycle ---------------------------------------------------
    def set_shard_down(self, shard: int) -> None:
        """Mark one placement shard unavailable: its device label slab goes
        to -1 (its rows stop matching any query) and requests owned by it
        answer empty with `degraded=True` while the rest of the batch
        answers normally — the batch never fails wholesale."""
        with self._guard():
            self.store.shard_down(shard)
        get_telemetry().event("shard_down", shard=int(shard))

    def set_shard_up(self, shard: int) -> None:
        """Bring a recovered shard back: restore its device labels from the
        host mirror and stop degrading its tenants' responses."""
        with self._guard():
            self.store.shard_up(shard)
        get_telemetry().event("shard_up", shard=int(shard))

    def attach_follower(self, sink, mode: str = "sync"):
        """Stream every sealed WAL segment to `sink` (a directory path or
        any object with put/has/list — see checkpoint/replication.py), so
        recovery survives losing this host's disk.  Returns the shipper."""
        if self.runtime is None:
            raise RuntimeError("attach_follower needs a lifecycle runtime "
                               "(construct the service with data_dir/runtime)")
        return self.runtime.attach_follower(sink, mode=mode)

    # -- stats ----------------------------------------------------------------------
    def stats(self) -> dict:
        """Store counters plus the operator's runtime view: `pending_depth`
        (buffered sessions), `wal_segments` (un-truncated log segments on
        disk) and `last_snapshot_age_s` (None until a snapshot exists)."""
        with self._guard():
            st = self.store.stats()
            if self.runtime is not None:
                st.update(self.runtime.stats())
            else:
                st.update({"pending_depth": st["pending"],
                           "wal_segments": 0,
                           "last_snapshot_age_s": None})
            return st

    def namespace_stats(self, namespace: str) -> dict:
        """Public per-namespace counters (no reaching into tenant state)."""
        with self._guard():
            t = self.store.get(namespace)
            if t is None:
                return {"triples": 0, "summaries": 0, "evicted": 0}
            return {"triples": len(t.triples),
                    "summaries": len(t.summaries),
                    "evicted": len(t.evicted)}


class NamespaceView:
    """MemoriMemory-compatible facade over one namespace of a MemoryService:
    MemoriClient (and anything else written against MemoriMemory's surface)
    runs on the shared service unchanged.  The namespace key IS the
    conversation scope, so record_session's conversation_id is subsumed by
    it (kept in the signature for drop-in compatibility)."""

    def __init__(self, service: MemoryService, namespace: str):
        self.service = service
        self.namespace = namespace
        self._seen_conversation_id: Optional[str] = None

    def record_session(self, conversation_id: str, session_id: str,
                       messages: Sequence[Message]):
        # the namespace key IS the scope, so conversation_id is otherwise
        # ignored — warn a drop-in caller who reuses one view across several
        # conversation_ids, since those scopes silently merge here
        if self._seen_conversation_id is None:
            self._seen_conversation_id = conversation_id
        elif conversation_id != self._seen_conversation_id:
            warnings.warn(
                f"NamespaceView({self.namespace!r}) saw conversation_id="
                f"{conversation_id!r} after {self._seen_conversation_id!r}: "
                "both record into the same namespace scope — use "
                f"service.namespace({conversation_id!r}) for a separate "
                "scope.", stacklevel=2)
        runtime = self.service.runtime
        if self.service.flush_every or (
                runtime is not None
                and runtime.policy.flush_interval_s is not None):
            # async batched ingestion: buffer until the count-based or
            # time-based flusher drains the queue (reads still see the
            # buffered sessions — retrieve flushes first).  No extraction
            # happens yet, so there is no per-session result.
            return self.service.enqueue(self.namespace, session_id, messages)
        return self.service.record(self.namespace, session_id, messages)

    def retrieve(self, query: str,
                 top_k: Optional[int] = None) -> RetrievedContext:
        return self.service.retrieve(self.namespace, query, top_k=top_k)

    def answer_prompt(self, question: str) -> Tuple[str, RetrievedContext]:
        return self.service.answer_prompt(self.namespace, question)

    def stats(self) -> dict:
        return self.service.namespace_stats(self.namespace)

    def close(self) -> None:
        """Shut the backing service's lifecycle runtime down (final flush +
        snapshot).  Idempotent and shared: the first closing view wins, so
        any client of a shared service may call it on exit."""
        self.service.close()
