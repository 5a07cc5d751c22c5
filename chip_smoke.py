"""Chip smoke: the served memory path on one TPU, checked against references.

    python3 chip_smoke.py             # one chip: serve (f32 + int8) + agent
    python3 chip_smoke.py --chips 4   # only the sharded bank over 4 chips

One chip.  For each device-bank residency (`quantize` none, then int8) the
server is built exactly as `python -m repro.launch.serve` builds it
(`build_server`: agent engine, MemoryService with the Pallas `topk_mips`
dense stage, MemoryScheduler, HTTP frontend on an ephemeral localhost
port).  Its bank is filled to 2^20 live rows at D=256: the bulk as many
seeded tenants whose flush records commit through `MemoryStore.apply_wal`
(the recovery path, which lands in the one write path, `_apply_flush`),
plus a few LoCoMo-shaped conversations recorded over `/v1/record`
(extraction, embedding, flush).  Client threads then send `/v1/retrieve`
batches, and the answers are checked:

* dense ids against an exact numpy MIPS over the host mirror (ids may
  differ only where the two scores lie within f32 dot rounding);
* the fused ranking against the scalar `rrf_fuse`;
* the graph ranking against `graph_expand_ref`, directly and through the
  fused graph-stage ranking.

Tokens per query and planted-question accuracy are printed.  The agent
model (`memori-agent`, full width, random weights from `--seed`) then
answers one retrieved context through `serving/engine.py`; its first-token
logits must match one plain full-sequence forward, and its greedy tokens
that forward's argmax.

Four chips (`--chips 4`): a `MemoryService(shards=4, mesh=...)` holds 2^20
rows per chip; its sharded search must equal the exact numpy reference and
the one-device masked search over the same rows, and the bank must spread
over all four devices.

The last line of stdout is `{"ok": true, "device": {...}}`; any failed
check raises.  Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import gc
import json
import os
import sys
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.api import RetrieveRequest  # noqa: E402
from repro.core.hybrid import rrf_fuse  # noqa: E402
from repro.core.memory import ANSWER_PROMPT  # noqa: E402
from repro.data import locomo_synth  # noqa: E402
from repro.kernels.ref import graph_expand_ref  # noqa: E402

D = 256
BULK_ROWS = 1 << 20
ROWS_PER_TENANT = 1024
CONVERSATIONS = 8
BATCH = 8                       # HTTP batch == scheduler tick == Q bucket
API_KEY, TENANT = "smoke-key", "smoke"
GRAPH_KNOBS = {"hops": 2, "edge_weights": [1.0, 0.9, 0.9],
               "dense_weight": 1.0, "sparse_weight": 0.7,
               "graph_weight": 0.6}
_U32 = 2.0 ** -24               # f32 unit roundoff
_PREDICATES = ("likes", "works as", "lives in", "visited", "owns",
               "learned", "plays", "cooked", "bought", "met")
_OBJECTS = (locomo_synth.FOODS + locomo_synth.HOBBIES + locomo_synth.CITIES
            + locomo_synth.PETS + locomo_synth.ITEMS + locomo_synth.PLACES
            + locomo_synth.SKILLS)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


# ---------------------------------------------------------------------------
# Bulk data: seeded flush records committed through the recovery path
# ---------------------------------------------------------------------------

def bulk_records(rows: int, rows_per_tenant: int, seed: int,
                 chunk: int = 8192, session_rows: int = 32):
    """Yield WAL flush records holding `rows` seeded triples spread over
    `rows / rows_per_tenant` tenants (namespaces `smoke/bulk<i>`), each
    row with a seeded unit vector."""
    rng = np.random.default_rng(seed)
    names = locomo_synth.NAMES
    for start in range(0, rows, chunk):
        stop = min(rows, start + chunk)
        vecs = rng.standard_normal((stop - start, D), dtype=np.float32)
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        subj = rng.integers(0, 2, stop - start)
        pred = rng.integers(0, len(_PREDICATES), stop - start)
        obj = rng.integers(0, 64, stop - start)
        sessions, r = [], start
        while r < stop:
            ten, off = divmod(r, rows_per_tenant)
            m = min(session_rows - off % session_rows, stop - r,
                    rows_per_tenant - off)
            ns, sid = f"{TENANT}/bulk{ten}", f"s{off // session_rows}"
            ts = 1.67e9 + 60.0 * off
            trs = []
            for j in range(r - start, r - start + m):
                o = int(obj[j])
                trs.append({
                    "subject": names[(ten + int(subj[j])) % len(names)],
                    "predicate": _PREDICATES[int(pred[j])],
                    "object": f"{_OBJECTS[(ten + o) % len(_OBJECTS)]} {o}",
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
               "dim": D, "vecs": vecs.astype("<f4").tobytes()}


def fill_bulk(service, rows: int, rows_per_tenant: int, seed: int) -> float:
    t0 = time.perf_counter()
    for rec in bulk_records(rows, rows_per_tenant, seed):
        service.store.apply_wal(rec)
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# HTTP client
# ---------------------------------------------------------------------------

def post(base: str, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json", "X-Api-Key": API_KEY})
    with urllib.request.urlopen(req, timeout=900) as r:
        return json.loads(r.read())


def retrieve_batches(base: str, batches, workers: int = 4):
    """POST every batch of query objects from `workers` client threads;
    returns the envelopes in batch order (each checked status ok)."""
    with cf.ThreadPoolExecutor(workers) as pool:
        futs = [pool.submit(post, base, "/v1/retrieve", {"queries": b})
                for b in batches]
        outs = [f.result() for f in futs]
    envs = []
    for out in outs:
        for env in out["responses"]:
            check(env["status"] == "ok", f"retrieve envelope {env}")
            envs.append(env)
    return envs


def batched(items, n: int = BATCH):
    """Split into batches of exactly n, padding the last with repeats of
    the first items (every tick then runs the same Q bucket)."""
    items = list(items)
    pad = (-len(items)) % n
    items = items + [items[i % len(items)] for i in range(pad)]
    return [items[i: i + n] for i in range(0, len(items), n)], len(items) - pad


# ---------------------------------------------------------------------------
# References
# ---------------------------------------------------------------------------

def effective_labels(vindex) -> np.ndarray:
    """(n,) host labels: namespace id for live resident rows, else -1."""
    return np.where(vindex.alive() & vindex.resident_mask(),
                    vindex.row_namespaces(), -1)


def exact_mips(bank, labels, q, ns: int, k: int) -> np.ndarray:
    """Exact masked MIPS in float64 over the host mirror: ids best-first,
    ties to the lower row id."""
    rows = np.flatnonzero(labels == ns)
    s = bank[rows].astype(np.float64) @ q.astype(np.float64)
    return rows[np.lexsort((rows, -s))[:k]]


def check_dense(got, ref, bank, labels, q, ns: int, what: str) -> None:
    """`got` equals `ref` except where the two rows' exact scores lie within
    the f32 dot-product rounding bound (D * u * |q| * |x|) of each other."""
    got = np.asarray(got, np.int64)
    check(got.shape == ref.shape, f"{what}: {got.size} ids, reference "
          f"{ref.size}")
    check(len(set(got.tolist())) == got.size, f"{what}: duplicate ids")
    check(bool(np.all(labels[got] == ns)), f"{what}: id outside namespace")
    q64 = q.astype(np.float64)
    qn = float(np.linalg.norm(q64))
    for j in np.flatnonzero(got != ref):
        a, b = bank[got[j]].astype(np.float64), bank[ref[j]].astype(np.float64)
        tol = D * _U32 * qn * (np.linalg.norm(a) + np.linalg.norm(b))
        check(abs(a @ q64 - b @ q64) <= tol,
              f"{what}: rank {j} holds row {got[j]} ({a @ q64:.9g}), "
              f"reference row {ref[j]} ({b @ q64:.9g})")


def check_scored(got_ids, got_scores, ref_ids, ref_scores, what: str,
                 ulps: int = 4) -> None:
    """Rankings with f32 scores (fusion, graph): ids equal position by
    position except at score ties within `ulps`, scores within `ulps`."""
    got_ids = [int(i) for i in got_ids]
    ref_ids = [int(i) for i in ref_ids]
    check(len(got_ids) == len(ref_ids),
          f"{what}: {len(got_ids)} ids, reference {len(ref_ids)}")
    ref_by_id = dict(zip(ref_ids, ref_scores))
    for j, (g, r, gs, rs) in enumerate(zip(got_ids, ref_ids, got_scores,
                                           ref_scores)):
        tol = ulps * 2.0 ** -23 * max(abs(rs), 1e-30)
        check(abs(gs - rs) <= tol,
              f"{what}: rank {j} score {gs!r} vs reference {rs!r}")
        if g != r:
            check(g in ref_by_id and abs(ref_by_id[g] - rs) <= tol,
                  f"{what}: rank {j} holds {g}, reference {r}")


def graph_reference(lanes, labels, rankings, ns: int, type_w, hops: int,
                    k: int, seed_k: int, decay: float):
    """`graph_expand_ref` for one query over the graph's host lanes
    `(src, dst, type, w, node_ns, row_sub, row_obj)`, run on the query's
    namespace subgraph (expansion never leaves a namespace, so this is
    exact): rows renumber monotonically, which keeps the (-score, row)
    order."""
    src, dst, et, w, node_ns, row_sub, row_obj = lanes
    keep = node_ns[src] == ns
    rows = np.flatnonzero(labels == ns)

    def local(r):
        r = np.asarray(r, np.int64)
        pos = np.clip(np.searchsorted(rows, r), 0, max(rows.size - 1, 0))
        return np.where((r >= 0) & (rows.size > 0) & (rows[pos] == r),
                        pos, -1)[None]

    ids, scores = graph_expand_ref(
        src[keep], dst[keep], et[keep], w[keep], node_ns, row_sub[rows],
        row_obj[rows], np.full(rows.size, ns, np.int32),
        [local(r) for r in rankings], np.asarray([ns], np.int32),
        np.asarray([type_w], np.float32), np.asarray([hops], np.int32),
        hops=hops, k=k, seed_k=seed_k, decay=decay)
    live = ids[0] >= 0
    return rows[ids[0][live]], scores[0][live]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def build(quantize: str, *, seed: int, max_len: int, host_demo: bool):
    from repro.launch import serve
    argv = ["--arch", "memori-agent", "--seed", str(seed),
            "--quantize", quantize, "--max-len", str(max_len),
            "--tick-interval", "0.002", "--max-batch", str(BATCH),
            "--http-port", "0", "--http-host", "127.0.0.1",
            "--api-keys", f"{API_KEY}={TENANT}"]
    if host_demo:
        argv.append("--host-demo")
    return serve.build_server(serve.parse_args(argv))


def phase_serve(quantize: str, *, bulk_rows: int = BULK_ROWS,
                rows_per_tenant: int = ROWS_PER_TENANT,
                conversations: int = CONVERSATIONS, noise_turns: int = 165,
                seed: int = 0, max_len: int = 2048,
                host_demo: bool = False):
    """Build the server, fill its bank, answer and check retrievals.
    Returns (report, server, (question, context text)); the caller closes
    the server."""
    import jax

    server = build(quantize, seed=seed, max_len=max_len, host_demo=host_demo)
    svc = server.service
    server.frontend.request_timeout_s = 900.0    # first ticks compile
    server.frontend.start()
    base = server.frontend.address
    fill_s = fill_bulk(svc, bulk_rows, rows_per_tenant, seed)
    log("fill", quantize=quantize, bulk_rows=bulk_rows,
        tenants=-(-bulk_rows // rows_per_tenant), seconds=f"{fill_s:.1f}")

    # the full path: LoCoMo-shaped conversations over /v1/record
    convs = [locomo_synth.generate_conversation(
        seed=seed + i, noise_turns=noise_turns, graph_chains=True)
        for i in range(conversations)]

    def record(conv):
        for sid, msgs in conv.sessions:
            env = post(base, "/v1/record", {
                "namespace": conv.conversation_id, "session_id": sid,
                "messages": [{"speaker": m.speaker, "text": m.text,
                              "timestamp": m.timestamp} for m in msgs]})
            check(env["status"] == "ok", f"record envelope {env}")
        return len(conv.sessions)

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(4) as pool:
        n_rec = sum(pool.map(record, convs))
    st = svc.stats()
    log("record", sessions=n_rec, seconds=f"{time.perf_counter() - t0:.1f}",
        live_rows=st["alive_rows"], capacity=svc.vindex.capacity)
    check(st["alive_rows"] >= bulk_rows, "bank below the bulk size")

    questions = [(f"{TENANT}/{c.conversation_id}", c.conversation_id, q)
                 for c in convs for q in c.questions]
    rng = np.random.default_rng(seed)
    n_bulk = -(-bulk_rows // rows_per_tenant)
    bulk_ns = sorted({0, n_bulk - 1, *rng.integers(0, n_bulk, 6).tolist()})
    bulk_q = [(f"{TENANT}/bulk{t}", f"bulk{t}",
               f"What does {locomo_synth.NAMES[t % 10]} like?")
              for t in bulk_ns]

    # query sets: budgeted hybrid for every planted question, budgeted
    # graph for the graph-category ones, raw rankings for the checks
    budget_qs = [{"namespace": ns, "query": q.question}
                 for _, ns, q in questions]
    graph_qs = [{"namespace": ns, "query": q.question,
                 "stages": ["dense", "sparse", "graph", "fuse", "budget"],
                 **GRAPH_KNOBS}
                for _, ns, q in questions
                if q.category in locomo_synth.GRAPH_CATEGORIES]
    probe = [(full, ns, q.question) for full, ns, q in questions[:16]] + \
        [(full, ns, text) for full, ns, text in bulk_q]
    pool_k = svc.pool
    raw = {
        "dense": [{"namespace": ns, "query": t, "stages": ["dense", "fuse"],
                   "top_k": pool_k} for _, ns, t in probe],
        "hybrid": [{"namespace": ns, "query": t,
                    "stages": ["dense", "sparse", "fuse"], "top_k": pool_k,
                    **{k: GRAPH_KNOBS[k] for k in ("dense_weight",
                                                   "sparse_weight")}}
                   for _, ns, t in probe],
        "graph": [{"namespace": ns, "query": t,
                   "stages": ["dense", "sparse", "graph", "fuse"],
                   "top_k": pool_k, **GRAPH_KNOBS} for _, ns, t in probe],
    }

    # warm every executable in-process through the service's own entry
    # (the scheduler), so the HTTP timings below are not compile time
    t0 = time.perf_counter()
    for qs in (budget_qs, graph_qs, *raw.values()):
        b, _ = batched(qs)
        svc.retrieve_batch([RetrieveRequest(
            namespace=f"{TENANT}/{o['namespace']}", query=o["query"],
            top_k=o.get("top_k"), stages=o.get("stages"), hops=o.get("hops"),
            edge_weights=o.get("edge_weights"),
            dense_weight=o.get("dense_weight"),
            sparse_weight=o.get("sparse_weight"),
            graph_weight=o.get("graph_weight")) for o in b[0]])
    log("warmup", quantize=quantize,
        seconds=f"{time.perf_counter() - t0:.1f}")

    # -- budgeted retrieval: tokens per query, planted-question accuracy --
    t0 = time.perf_counter()
    b, n = batched(budget_qs)
    ctx = retrieve_batches(base, b)[:n]
    bg, ng = batched(graph_qs)
    gctx = retrieve_batches(base, bg)[:ng]
    http_s = time.perf_counter() - t0
    tokens = [e["token_count"] for e in ctx]
    hits = [locomo_synth.judge(q, locomo_synth.oracle_read(
        q, e["payload"]["text"])) for (_, _, q), e in zip(questions, ctx)]
    gq = [q for _, _, q in questions
          if q.category in locomo_synth.GRAPH_CATEGORIES]
    ghits = [locomo_synth.judge(q, locomo_synth.oracle_read(
        q, e["payload"]["text"])) for q, e in zip(gq, gctx)]
    check(all(e["payload"]["kind"] == "retrieved_context"
              for e in ctx + gctx), "budgeted retrieve returned raw payload")
    log("retrieve", quantize=quantize, requests=n + ng,
        seconds=f"{http_s:.2f}",
        tokens_per_query=f"{np.mean(tokens):.1f}",
        accuracy=f"{np.mean(hits):.3f}",
        graph_stage_accuracy=f"{np.mean(ghits) if ghits else 0.0:.3f}")

    # -- raw rankings over HTTP, checked against the references ----------
    envs = {}
    for name, qs in raw.items():
        b, _ = batched(qs)
        envs[name] = retrieve_batches(base, b)
    probe, _ = batched(probe)                    # the same padded order
    probe = [p for b in probe for p in b]
    vindex = svc.vindex
    bank, labels = vindex.bank, effective_labels(vindex)
    ns_of = {full: svc.store.get(full).ns_id for full, _, _ in probe}
    qv = np.asarray(svc.embedder.embed_texts([t for _, _, t in probe]),
                    np.float32)
    dense_rank = []
    for j, (full, _, text) in enumerate(probe):
        got = envs["dense"][j]["payload"]["row_ids"]
        ref = exact_mips(bank, labels, qv[j], ns_of[full], pool_k)
        check_dense(got, ref, bank, labels, qv[j], ns_of[full],
                    f"dense[{quantize}] {full!r} {text!r}")
        dense_rank.append(list(got) + [-1] * (pool_k - len(got)))
    dense_rank = np.asarray(dense_rank, np.int32)

    # sparse rankings: the BM25 index's own top-k over each tick's batch,
    # scored exactly as the tick scored it (same queries, order and Q)
    q_ns = np.asarray([ns_of[full] for full, _, _ in probe], np.int32)
    sparse_rank = np.concatenate([
        svc.bm25.topk_batch([t for _, _, t in probe[i: i + BATCH]],
                            k=pool_k, namespaces=q_ns[i: i + BATCH])[1]
        for i in range(0, len(probe), BATCH)]).astype(np.int32)
    w_d, w_s, w_g = (GRAPH_KNOBS["dense_weight"],
                     GRAPH_KNOBS["sparse_weight"],
                     GRAPH_KNOBS["graph_weight"])
    g, plan = svc.store.graph, svc.plan
    lanes = (*g.edges(), g.node_ns(), *g.row_incidence())
    # the device expansion itself, per tick-sized batch of seed rankings
    dev_graph = [np.asarray(a) for a in zip(*[
        g.expand([dense_rank[i: i + BATCH], sparse_rank[i: i + BATCH]],
                 q_ns[i: i + BATCH], svc.store.row_namespaces_device(),
                 np.tile(np.float32(GRAPH_KNOBS["edge_weights"]), (BATCH, 1)),
                 np.full(BATCH, GRAPH_KNOBS["hops"], np.int32), k=pool_k,
                 max_hops=GRAPH_KNOBS["hops"], seed_k=plan.graph_seed_k,
                 decay=plan.graph_decay)
        for i in range(0, len(probe), BATCH)])]
    dev_ids, dev_scores = (np.concatenate(a) for a in dev_graph)
    n_graph_rows = 0
    for j, (full, _, text) in enumerate(probe):
        what = f"{full!r} {text!r}"
        env = envs["hybrid"][j]["payload"]
        ref = rrf_fuse([dense_rank[j], sparse_rank[j]], [w_d, w_s])[:pool_k]
        check_scored(env["row_ids"], env["scores"], [d for d, _ in ref],
                     [s for _, s in ref], f"fused {what}")
        gids, gsc = graph_reference(
            lanes, labels, [dense_rank[j], sparse_rank[j]], q_ns[j],
            GRAPH_KNOBS["edge_weights"], GRAPH_KNOBS["hops"], pool_k,
            plan.graph_seed_k, plan.graph_decay)
        n_graph_rows += len(gids)
        live = dev_ids[j] >= 0
        check_scored(dev_ids[j][live], dev_scores[j][live], gids, gsc,
                     f"graph {what}")
        env = envs["graph"][j]["payload"]
        ref = rrf_fuse([dense_rank[j], sparse_rank[j], gids],
                       [w_d, w_s, w_g])[:pool_k]
        check_scored(env["row_ids"], env["scores"], [d for d, _ in ref],
                     [s for _, s in ref], f"graph-fused {what}")
    log("check", quantize=quantize, probes=len(probe), dense="equal",
        fused="equal", graph="equal", graph_rows=n_graph_rows)

    if jax.devices()[0].platform == "tpu":
        # no hidden fallback: the dense executable, lowered with the flags
        # the served path passes, holds the Mosaic kernel, not the
        # interpreter or the jnp reference
        from repro.common.utils import next_pow2
        from repro.core import vector_index as vi_mod
        from repro.kernels import ops as kops
        q = qv[:BATCH]
        fn, args = (vi_mod._search_device, (vindex._bank_dev,)) \
            if quantize == "none" else \
            (vi_mod._search_device_quant, (vindex._bank_dev,
                                           vindex._scales_dev))
        k = pool_k if quantize == "none" else min(
            vindex.capacity, next_pow2(pool_k * vindex.rescore))
        txt = fn.lower(*args, vindex._labels_dev, q, q_ns[:BATCH],
                       np.int32(vindex.n), np.int32(0), k=k,
                       use_kernel=vindex.use_kernel,
                       interpret=kops._interpret_default(),
                       uniform=False).as_text()
        check("tpu_custom_call" in txt, "dense stage lowered without the "
              "topk_mips kernel")
        mem = jax.devices()[0].memory_stats() or {}
        log("device", quantize=quantize,
            bytes_in_use=mem.get("bytes_in_use"),
            peak_bytes_in_use=mem.get("peak_bytes_in_use"))
    report = {"quantize": quantize, "live_rows": st["alive_rows"],
              "tokens_per_query": float(np.mean(tokens)),
              "accuracy": float(np.mean(hits))}
    first = ctx[0]["payload"]["text"], questions[0][2].question
    return report, server, first


def phase_agent(server, context: str, question: str, *, new_tokens: int = 4):
    """The agent model answers one retrieved context through the serving
    engine; first-token logits and greedy tokens against a plain forward.
    Both run at full f32 matmul precision (the model computes in f32, and
    the TPU's default f32 dot rounds operands to bf16), so the comparison
    checks the engine's cache and positions, not two roundings."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _agent_check(server, context, question, new_tokens)


def _agent_check(server, context: str, question: str, new_tokens: int):
    import jax
    import jax.numpy as jnp

    from repro.models import transformer
    from repro.models.layers import embedding
    from repro.serving.engine import Engine
    from repro.serving.requests import Request
    from repro.serving.scheduler import ContinuousBatcher

    base = server.engine
    model, params, cfg = base.model, base.params, base.model.cfg
    engine = Engine(model, params, max_len=base.max_len, slots=1,
                    tokenizer=base.tokenizer)            # greedy sampler
    prompt = ANSWER_PROMPT.format(memories=context, question=question)
    toks = engine.tokenizer.encode(prompt)[: engine.max_len - new_tokens - 1]
    first, _ = engine._prefill(params, {"tokens": jnp.asarray([toks],
                                                              jnp.int32)})
    # eos_id=-1: random weights may emit EOS; the check wants every step
    out = ContinuousBatcher(engine).run([Request(toks, new_tokens,
                                                 eos_id=-1)])
    gen = list(out.values())[0].tokens
    check(len(gen) == new_tokens, f"engine produced {len(gen)} tokens")

    @jax.jit
    def full_forward(params, tokens):
        x = embedding.embed(params["embed"], cfg, tokens)
        pos = jnp.arange(tokens.shape[1])[None]
        h, _, _ = transformer.decoder_apply(
            params, cfg, x, mode="train", positions=pos, mask_kind="causal",
            remat=False)
        return embedding.logits(params["embed"], cfg, h)

    S = len(toks)
    ref = np.asarray(full_forward(
        params, jnp.asarray([toks + gen[:-1]], jnp.int32)))[0]
    first = np.asarray(first).reshape(-1)
    check(bool(np.isfinite(first).all()), "non-finite first-token logits")
    err = float(np.max(np.abs(first - ref[S - 1])))
    np.testing.assert_allclose(first, ref[S - 1], rtol=2e-3, atol=2e-3)
    for i, t in enumerate(gen):
        row = ref[S - 1 + i]
        top2 = np.sort(row)[-2:]
        check(int(t) == int(np.argmax(row)) or top2[1] - top2[0] <= 2e-3,
              f"greedy token {i}: engine {t}, forward {int(np.argmax(row))}")
    log("agent", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        prompt_tokens=S, new_tokens=len(gen), first_logits_max_abs_err=err,
        result="equal")
    return {"prompt_tokens": S, "first_logits_max_abs_err": err}


def phase_sharded(n_devices: int, *, rows_per_device: int = BULK_ROWS,
                  rows_per_tenant: int = ROWS_PER_TENANT, seed: int = 0,
                  queries: int = 16):
    """Sharded bank over `n_devices`: placement, and results equal to the
    exact reference and the one-device masked search over the same rows."""
    import jax

    from repro.core import MemoryService
    from repro.core.embedder import HashEmbedder
    from repro.launch.mesh import make_host_mesh

    check(len(jax.devices()) >= n_devices,
          f"{len(jax.devices())} devices, need {n_devices}")
    mesh = make_host_mesh(n_devices, 1)
    svc = MemoryService(HashEmbedder(), budget=800, shards=n_devices,
                        mesh=mesh)
    rows = n_devices * rows_per_device
    fill_s = fill_bulk(svc, rows, rows_per_tenant, seed)
    n_ten = rows // rows_per_tenant
    log("fill", shards=n_devices, rows=rows, tenants=n_ten,
        seconds=f"{fill_s:.1f}")
    rng = np.random.default_rng(seed + 1)
    tenants = rng.permutation(n_ten)[:queries]
    texts = [f"What does {locomo_synth.NAMES[t % 10]} own?" for t in tenants]
    reqs = [RetrieveRequest(namespace=f"{TENANT}/bulk{t}", query=x,
                            stages=("dense", "fuse"), top_k=svc.pool)
            for t, x in zip(tenants, texts)]
    t0 = time.perf_counter()
    out = svc.retrieve_batch(reqs)
    log("search", shards=n_devices, queries=len(reqs),
        seconds_with_compile=f"{time.perf_counter() - t0:.1f}")

    bank_dev = svc.store.sharded.bank_device()
    devs = {s.device for s in bank_dev.addressable_shards}
    shapes = sorted({tuple(s.data.shape) for s in bank_dev.addressable_shards})
    check(len(bank_dev.sharding.device_set) == n_devices
          and len(devs) == n_devices,
          f"bank spans {len(bank_dev.sharding.device_set)} devices")
    per_dev = svc.store.sharded.C
    check(shapes == [(per_dev, D)], f"shard shapes {shapes}")
    check(per_dev >= rows_per_device, f"per-device capacity {per_dev}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in
              sorted(devs, key=lambda d: d.id)]
    if jax.devices()[0].platform == "tpu":
        check(all(b is not None and b >= per_dev * D * 4 for b in in_use),
              f"device bytes in use {in_use}")
    log("placement", devices=len(devs), rows_per_device=per_dev,
        bytes_in_use=in_use,
        counts=svc.store.sharded.stats()["per_shard_rows"])

    vindex = svc.vindex
    bank, labels = vindex.bank, effective_labels(vindex)
    qv = np.asarray(svc.embedder.embed_texts(texts), np.float32)
    q_ns = np.asarray([svc.store.get(r.namespace).ns_id for r in reqs],
                      np.int32)
    _, one_dev = vindex.search_batch(qv, q_ns, k=svc.pool)
    one_dev = np.asarray(one_dev)
    for j, (r, raw) in enumerate(zip(reqs, out)):
        ref = exact_mips(bank, labels, qv[j], int(q_ns[j]), svc.pool)
        check_dense(raw.row_ids, ref, bank, labels, qv[j], int(q_ns[j]),
                    f"sharded {r.namespace!r}")
        check_dense(raw.row_ids, one_dev[j][one_dev[j] >= 0], bank, labels,
                    qv[j], int(q_ns[j]), f"sharded vs one-device "
                    f"{r.namespace!r}")
    log("check", shards=n_devices, queries=len(reqs),
        reference="exact numpy + one-device masked search", result="equal")
    return {"rows": rows, "per_device": per_dev}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.common.utils import init_compilation_cache
    log("start", device_kind=dev.device_kind, devices=len(jax.devices()),
        compile_cache=init_compilation_cache())
    if args.chips == 4:
        phase_sharded(4, seed=args.seed)
    else:
        first = None
        for quantize in ("none", "int8"):
            report, server, ctx = phase_serve(quantize, seed=args.seed)
            if first is None:
                first = ctx
                phase_agent(server, *first)
            server.frontend.close()
            server.service.close()
            del server
            gc.collect()
            log("serve", **report)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
