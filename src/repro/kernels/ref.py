"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38
# the MIPS oracles contract at full f32 precision, like the kernels they
# check (the TPU's default f32 dot rounds operands to bf16)
_EXACT = jax.lax.Precision.HIGHEST


def topk_mips_ref(queries, bank, k: int = 32, n_valid=None):
    """queries (Q,D), bank (N,D) -> (scores (Q,k) f32, indices (Q,k) i32).
    With `n_valid` (traced i32 scalar), rows >= n_valid are padding: they
    score NEG_INF and report index -1 — matching the kernel's stable-shape
    contract over capacity-padded banks."""
    s = jnp.einsum("qd,nd->qn", queries.astype(jnp.float32),
                   bank.astype(jnp.float32), precision=_EXACT)
    if n_valid is not None:
        col = jnp.arange(bank.shape[0], dtype=jnp.int32)[None, :]
        s = jnp.where(col < n_valid, s, NEG_INF)
    scores, idx = jax.lax.top_k(s, k)
    if n_valid is not None:
        idx = jnp.where(scores > NEG_INF / 2, idx, -1)
    return scores, idx.astype(jnp.int32)


def quantize_rows_ref(bank):
    """Symmetric per-row int8 quantization (the contract the quantized
    kernels score against): scale = max|row| / 127, q = round(row / scale)
    clipped to [-127, 127]; an all-zero row gets scale 0 and zero codes.
    Returns (codes int8 (N, D), scales f32 (N,)).  Shared by the
    VectorIndex quantizer and the oracle tests — per-element dequant error
    is bounded by scale/2."""
    bank = jnp.asarray(bank, jnp.float32)
    amax = jnp.max(jnp.abs(bank), axis=1)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    codes = jnp.clip(jnp.round(bank * inv[:, None]), -127, 127)
    return codes.astype(jnp.int8), scale


def _quant_scores(queries, bank_i8, scales):
    """(Q, N) f32 scores in the fused kernel's exact operation order:
    contract the int8 codes in f32, THEN multiply by the row scale —
    `(q · row_i8) * scale`, not `q · (scale * row_i8)` — so oracle and
    kernel agree to the same rounding and index comparisons stay exact."""
    s = jnp.einsum("qd,nd->qn", jnp.asarray(queries, jnp.float32),
                   jnp.asarray(bank_i8).astype(jnp.float32), precision=_EXACT)
    return s * jnp.asarray(scales, jnp.float32)[None, :]


def topk_mips_quant_ref(queries, bank_i8, scales, k: int = 32, n_valid=None):
    """Quantized-MIPS oracle: top-k over the fused dequant scores."""
    s = _quant_scores(queries, bank_i8, scales)
    if n_valid is not None:
        col = jnp.arange(bank_i8.shape[0], dtype=jnp.int32)[None, :]
        s = jnp.where(col < n_valid, s, NEG_INF)
    scores, idx = jax.lax.top_k(s, k)
    if n_valid is not None:
        idx = jnp.where(scores > NEG_INF / 2, idx, -1)
    return scores, idx.astype(jnp.int32)


def topk_mips_quant_masked_ref(queries, bank_i8, scales, q_ns, bank_ns,
                               k: int = 32, n_valid=None):
    """Namespace-masked quantized-MIPS oracle (see topk_mips_quant_ref)."""
    s = _quant_scores(queries, bank_i8, scales)
    ok = jnp.asarray(q_ns, jnp.int32)[:, None] == \
        jnp.asarray(bank_ns, jnp.int32)[None, :]
    if n_valid is not None:
        col = jnp.arange(bank_i8.shape[0], dtype=jnp.int32)[None, :]
        ok = ok & (col < n_valid)
    s = jnp.where(ok, s, NEG_INF)
    scores, idx = jax.lax.top_k(s, k)
    idx = jnp.where(scores > NEG_INF / 2, idx, -1)
    return scores, idx.astype(jnp.int32)


def topk_mips_masked_ref(queries, bank, q_ns, bank_ns, k: int = 32,
                         n_valid=None):
    """Namespace-masked MIPS oracle: cross-namespace scores become NEG_INF
    and their indices -1 (matching the kernel, whose running top-k never
    admits a masked column).  q_ns (Q,) i32 >= 0; bank_ns (N,) i32 with -1
    marking tombstoned rows.  `n_valid` bounds the live bank prefix of a
    capacity-padded bank, as in topk_mips_ref."""
    s = jnp.einsum("qd,nd->qn", queries.astype(jnp.float32),
                   bank.astype(jnp.float32), precision=_EXACT)
    ok = jnp.asarray(q_ns, jnp.int32)[:, None] == \
        jnp.asarray(bank_ns, jnp.int32)[None, :]
    if n_valid is not None:
        col = jnp.arange(bank.shape[0], dtype=jnp.int32)[None, :]
        ok = ok & (col < n_valid)
    s = jnp.where(ok, s, NEG_INF)
    scores, idx = jax.lax.top_k(s, k)
    idx = jnp.where(scores > NEG_INF / 2, idx, -1)
    return scores, idx.astype(jnp.int32)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        scale=None):
    """q: (B,K,G,S,D); k,v: (B,K,T,D) -> (B,K,G,S,D)."""
    B, K, G, S, D = q.shape
    T = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bkgsd,bktd->bkgst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok = ok & (k_pos <= q_pos)
    if window > 0:
        ok = ok & (k_pos > q_pos - window)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(q, k, v, kv_len, *, scale=None, window: int = 0):
    """q: (B,K,G,D); k,v: (B,K,T,D); kv_len (B,) -> (B,K,G,D)."""
    B, K, G, D = q.shape
    T = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bkgd,bktd->bkgt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(T)[None, None, None, :]
    kl = kv_len[:, None, None, None]
    ok = pos < kl
    if window > 0:
        ok = ok & (pos > kl - 1 - window)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def graph_expand_ref(edge_src, edge_dst, edge_type, edge_w, node_ns,
                     row_sub, row_obj, row_labels, rankings, q_ns, type_w,
                     hops_b, *, hops: int, k: int, seed_k: int,
                     decay: float):
    """Scalar BFS oracle for core/graph._expand_device — the parity
    contract for the batched k-hop expansion.  Per-request max-product
    relaxation over the edge list with the SAME float32 operation order as
    the device kernel:

        we = type_w[b, etype] * edge_w          # f32 * f32
        c  = F[src] * we
        c  = c * decay
        c  = c / out_degree(src)

    combined by max, so accumulation order cannot matter and scores match
    the device scatter-max bit-exactly.  Inputs are the HOST mirrors (tight
    or padded — only the first n entries of each lane are read, as passed);
    `rankings` a sequence of (B, P_i) int arrays (-1-padded best-first),
    `row_labels` (n_rows_total,) effective labels (-1 = dead), `type_w`
    (B, 3) f32, `hops_b` (B,) per-request hop counts.  Returns (ids (B, k)
    i32 -1-padded, scores (B, k) f32) ordered by (-score, row id)."""
    import numpy as np
    edge_src = np.asarray(edge_src, np.int32)
    edge_dst = np.asarray(edge_dst, np.int32)
    edge_type = np.asarray(edge_type, np.int32)
    edge_w = np.asarray(edge_w, np.float32)
    node_ns = np.asarray(node_ns, np.int32)
    row_sub = np.asarray(row_sub, np.int32)
    row_obj = np.asarray(row_obj, np.int32)
    row_labels = np.asarray(row_labels, np.int32)
    q_ns = np.asarray(q_ns, np.int32)
    type_w = np.asarray(type_w, np.float32)
    hops_b = np.asarray(hops_b, np.int32)
    decay32 = np.float32(decay)
    B = q_ns.shape[0]
    n_nodes = node_ns.shape[0]
    n_rows = row_sub.shape[0]
    deg = np.bincount(edge_src, minlength=max(1, n_nodes)).astype(np.int64)
    out_ids = np.full((B, k), -1, np.int32)
    out_scores = np.zeros((B, k), np.float32)
    for b in range(B):
        ns = int(q_ns[b])
        seeds = {}                                # node -> f32 activation
        for r in rankings:
            for row in np.asarray(r[b][:seed_k], np.int64):
                row = int(row)
                if row < 0 or row >= n_rows or row >= row_labels.shape[0]:
                    continue
                if int(row_labels[row]) != ns:
                    continue
                for node in (int(row_sub[row]), int(row_obj[row])):
                    if node >= 0 and int(node_ns[node]) == ns:
                        seeds[node] = np.float32(1.0)
        frontier = dict(seeds)
        # seed nodes never score rows — neither their hop-0 activation nor
        # any hop>=1 re-activation (the device kernel masks them the same
        # way) — `act` holds newly discovered nodes only
        act = {}
        for h in range(int(min(hops_b[b], hops))):
            nxt = {}
            for e in range(edge_src.shape[0]):
                s, d = int(edge_src[e]), int(edge_dst[e])
                f = frontier.get(s)
                if f is None or int(node_ns[d]) != ns:
                    continue
                we = type_w[b, int(edge_type[e])] * edge_w[e]
                c = f * we
                c = c * decay32
                c = c / np.float32(max(int(deg[s]), 1))
                if c > nxt.get(d, np.float32(0.0)):
                    nxt[d] = c
            for node, sc in nxt.items():
                if sc > act.get(node, np.float32(0.0)):
                    act[node] = sc
            frontier = nxt
            if not frontier:
                break
        for node in seeds:
            act.pop(node, None)
        scored = []
        for row in range(n_rows):
            if row >= row_labels.shape[0] or int(row_labels[row]) != ns:
                continue
            sc = np.float32(0.0)
            for node in (int(row_sub[row]), int(row_obj[row])):
                if node >= 0:
                    sc = max(sc, act.get(node, np.float32(0.0)))
            if sc > 0:
                scored.append((-sc, row))
        scored.sort()
        for i, (negsc, row) in enumerate(scored[:k]):
            out_ids[b, i] = row
            out_scores[b, i] = -negsc
    return out_ids, out_scores
