"""Graph-stage scoreboard: recall uplift + latency cost of k-hop expansion.

Plants graph-answerable chains (`locomo_synth.generate_conversation(...,
graph_chains=True)`: multi-hop entity chains and within-session temporal
succession) into a multi-tenant MemoryService, then asks every
GRAPH_CATEGORY question twice through the RAW plans — flat hybrid
(dense+sparse+fuse) vs graph-expanded (dense+sparse+graph+fuse) — and
scores **triple-level support recall**: a question counts as recalled when
the returned triples textually contain each of its evidence pairs.  Raw
plans (no token budgeting, no summaries) isolate exactly what the ISSUE
asks for: does the expansion stage surface chain triples the flat ranking
misses, and what does the extra launch cost?

Also asserts the device-residency contract end-to-end: after warmup, the
whole graph-plan batch re-executes with ZERO recompiles.

    JAX_PLATFORMS=cpu python benchmarks/graph_bench.py --json BENCH_graph.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.common.utils import count_compiles
from repro.core.api import RetrievalPlan, RetrieveRequest
from repro.core.embedder import HashEmbedder
from repro.core.service import MemoryService
from repro.data.locomo_synth import GRAPH_CATEGORIES, generate_conversation


def build(seeds, n_sessions, noise_turns):
    svc = MemoryService(HashEmbedder(), use_kernel=False, top_k=10)
    questions = []          # (namespace, Question)
    for seed in seeds:
        conv = generate_conversation(seed=seed, n_sessions=n_sessions,
                                     noise_turns=noise_turns,
                                     graph_chains=True)
        ns = conv.conversation_id
        for sid, msgs in conv.sessions:
            svc.record(ns, sid, msgs)
        questions.extend((ns, q) for q in conv.questions
                         if q.category in GRAPH_CATEGORIES)
    svc.flush()
    return svc, questions


def recalled(svc, ns, q, raw) -> bool:
    t = svc.store.get(ns)
    texts = [t.triples.get(tid).text().lower() for tid in raw.triple_ids]
    need = len(q.supports) if q.min_supports < 0 else q.min_supports
    hits = sum(1 for sup in q.supports
               if any(all(term.lower() in tx for term in sup)
                      for tx in texts))
    return hits >= need


def run_plan(svc, questions, plan, hops, repeats):
    reqs = [RetrieveRequest(ns, q.question, top_k=10,
                            hops=hops if plan.wants_graph else None)
            for ns, q in questions]
    outs = svc.execute(reqs, plan=plan)          # warm (compile + measure recall)
    per_cat = {c: [0, 0] for c in GRAPH_CATEGORIES}
    for (ns, q), raw in zip(questions, outs):
        per_cat[q.category][0] += recalled(svc, ns, q, raw)
        per_cat[q.category][1] += 1
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        svc.execute(reqs, plan=plan)
        times.append(time.perf_counter() - t0)
    times.sort()
    lat_ms = 1e3 * times[len(times) // 2]
    recall = {c: h / max(1, n) for c, (h, n) in per_cat.items()}
    overall = (sum(h for h, _ in per_cat.values())
               / max(1, sum(n for _, n in per_cat.values())))
    return reqs, recall, overall, lat_ms


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", default="0,1,2",
                    help="comma-separated conversation seeds")
    ap.add_argument("--sessions", type=int, default=6)
    ap.add_argument("--noise", type=int, default=40)
    ap.add_argument("--hops", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--assert-uplift", type=float, default=0.1,
                    help="required overall recall gain of graph over flat")
    ap.add_argument("--assert-latency-factor", type=float, default=5.0,
                    help="graph batch latency budget, as a multiple of flat")
    ap.add_argument("--json", dest="json_path", default=None)
    args = ap.parse_args(argv)

    seeds = [int(s) for s in args.seeds.split(",") if s != ""]
    svc, questions = build(seeds, args.sessions, args.noise)
    g = svc.store.graph
    print(f"store: {svc.store.vindex.n} rows, graph {g.n_nodes} nodes / "
          f"{g.n_edges} edges {g.edge_type_counts()}, "
          f"{len(questions)} graph questions")

    flat_plan = RetrievalPlan.raw()
    graph_plan = RetrievalPlan.graph_expanded(budget=False)
    _, flat_recall, flat_overall, flat_ms = run_plan(
        svc, questions, flat_plan, args.hops, args.repeats)
    graph_reqs, graph_recall, graph_overall, graph_ms = run_plan(
        svc, questions, graph_plan, args.hops, args.repeats)

    # steady state: with edge lanes growing WITHIN their capacity bucket,
    # the warmed graph-plan batch re-executes compile-free
    ns0 = questions[0][0]
    svc.store.link(ns0, "bench probe a", "bench probe b", "entity")
    with count_compiles() as cc:
        svc.execute(graph_reqs, plan=graph_plan)
        svc.store.link(ns0, "bench probe c", "bench probe d", "entity")
        svc.execute(graph_reqs, plan=graph_plan)
    zero_recompile = cc.count == 0

    uplift = graph_overall - flat_overall
    latency_factor = graph_ms / max(1e-9, flat_ms)
    result = {
        "bench": "graph_expansion",
        "questions": len(questions),
        "graph": {"nodes": g.n_nodes, "edges": g.n_edges,
                  **{f"edges_{k}": v
                     for k, v in g.edge_type_counts().items()}},
        "recall": {"flat": {"overall": flat_overall, **flat_recall},
                   "graph": {"overall": graph_overall, **graph_recall}},
        "uplift": uplift,
        "latency_ms": {"flat_batch_p50": flat_ms,
                       "graph_batch_p50": graph_ms,
                       "factor": latency_factor},
        "zero_recompile_steady_state": zero_recompile,
        "asserted": {"uplift_min": args.assert_uplift,
                     "latency_factor_max": args.assert_latency_factor},
    }
    print(json.dumps(result, indent=2))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(result, f, indent=2)

    failures = []
    if not zero_recompile:
        failures.append(f"steady-state graph batch recompiled {cc.count}x")
    if uplift < args.assert_uplift:
        failures.append(f"recall uplift {uplift:.3f} < {args.assert_uplift}")
    if latency_factor > args.assert_latency_factor:
        failures.append(f"latency factor {latency_factor:.2f}x > "
                        f"{args.assert_latency_factor}x budget")
    if failures:
        print("FAIL: " + "; ".join(failures))
        return 1
    print(f"OK: recall {flat_overall:.3f} -> {graph_overall:.3f} "
          f"(+{uplift:.3f}) at {latency_factor:.2f}x flat latency, "
          f"zero recompiles")
    return 0


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    sys.exit(main())
