"""Fused top-k maximum-inner-product search over the Memori triple bank.

This is the TPU-native replacement for the paper's FAISS index (DESIGN.md
§3): the embedding bank is streamed HBM→VMEM in (block_n, D) tiles, scored
against the resident query tile on the MXU, and a running top-k (scores +
global indices) is maintained in the revisited output block across the
sequential bank-block grid dimension.

Exact search is deliberate: Advanced Augmentation compresses dialogue to
~10⁶-scale triples, small enough that exact MIPS beats pointer-chasing ANN
structures on TPU.

Grid: (num_q_blocks, num_bank_blocks)   — bank dim innermost/sequential.
Per-step top-k merge is an unrolled k-iteration argmax sweep (Pallas-TPU
friendly: no sort, no scatter).

Multi-tenant extension: when per-query and per-bank-row namespace ids are
supplied, cross-namespace hits are masked to NEG_INF *before* the top-k
merge, so one kernel launch serves a whole batch of tenants against one
packed bank (the MemoryService batched-retrieval path).  Rows with
namespace -1 are tombstones and match no query.  Without namespaces the
original kernel runs unchanged.

Masked launches skip the bank blocks no query of a tile can match.  Before
the `pallas_call`, in the same jit, one reduce fusion flags each pair of
query tile i and bank block j: the flag is set only where block j holds a
row below `n_valid` whose label is >= 0 and equals the namespace of some
query in tile i (exact membership, not a label range, so a bank whose
tenants' rows interleave still skips what it can).  From the flags comes,
per tile, the index of the next flagged block at or after j (clamped to
the last flagged block, or 0 if none is).  Both ride in as scalar-prefetch
operands: the bank, scale and label `index_map`s return the next flagged
block, so a run of skipped steps keeps one block index and issues no DMA
while the block needed next is fetched early; the body runs the dot, the
mask and the merge under `pl.when(flag)`.  The skip is exact, bit for bit:
a skipped block would have scored NEG_INF for every query of its tile, and
merging an all-NEG_INF block re-emits the running list unchanged (running
columns win argmax ties; a NEG_INF maximum emits -1).  Within a flagged
block the grid's j, never the remapped index, numbers the columns.

Stable-shape contract (the device-resident retrieval engine): the number of
valid bank rows rides along as a *traced* SMEM scalar, never a trace-time
constant.  Callers may hand in a capacity-padded bank (rows >= n_valid are
garbage) and grow `n_valid` append after append without triggering a single
recompile — the executable is keyed only on the padded shapes, which the
VectorIndex changes exclusively at power-of-two capacity boundaries.

Quantized extension (`scales=`): the bank may arrive as int8 with one f32
scale per row (symmetric per-row quantization: row_f32 ≈ scale * row_i8).
Dequantization is FUSED into the block loop — the kernel contracts the
int8 tile against the f32 query tile with f32 accumulation and multiplies
the score columns by the row scales afterwards, which is exactly
q · (scale * row_i8) without ever materializing an f32 bank tile.  The
bank read drops from 4 bytes/element to 1 (+4 bytes/row for the scale), so
the memory-bound scan moves ~4x less data and the same HBM holds ~4x more
resident rows.  Same grid, same masked/`n_valid`-traced contract, same
launch count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
# full f32 contraction: the TPU's default f32 dot rounds its operands to
# bf16, which would break exact parity with the host-side references
_EXACT = jax.lax.Precision.HIGHEST


def _merge_topk(scores_ref, idx_ref, s, col, k: int):
    """Merge block scores s (Qb, Nb) with the running (Qb, k) top-k refs."""
    all_s = jnp.concatenate([scores_ref[...], s], axis=1)
    all_i = jnp.concatenate([idx_ref[...], col], axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, all_s.shape, 1)
    for j in range(k):
        m = jnp.max(all_s, axis=1)
        am = jnp.argmax(all_s, axis=1)
        hit = cols == am[:, None]
        sel_i = jnp.sum(jnp.where(hit, all_i, 0), axis=1)
        scores_ref[:, j] = m
        # once a query's candidates are exhausted, every remaining max is the
        # NEG_INF sentinel and argmax degenerates to column 0 — whose all_i
        # entry is a previously-selected index at grid steps nb > 0.  Emit -1
        # instead (matching the oracle); real dot products never reach the
        # sentinel, so live slots are unaffected.
        idx_ref[:, j] = jnp.where(m > NEG_INF / 2, sel_i, -1)
        all_s = jnp.where(hit, NEG_INF, all_s)


def _kernel(nvalid_ref, q_ref, bank_ref, scores_ref, idx_ref, *, block_n: int,
            k: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    q = q_ref[...]
    b = bank_ref[...]
    s = jax.lax.dot_general(q, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_EXACT)                       # (Qb, Nb)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + nb * block_n
    s = jnp.where(col < nvalid_ref[0], s, NEG_INF)  # mask padded bank rows
    _merge_topk(scores_ref, idx_ref, s, col, k)


def _kernel_masked(nvalid_ref, flags_ref, nxt_ref, q_ref, bank_ref, qns_ref,
                   bns_ref, scores_ref, idx_ref, *, block_n: int, k: int):
    del nxt_ref                     # read by the index_maps only
    qb, nb = pl.program_id(0), pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    @pl.when(flags_ref[qb * pl.num_programs(1) + nb] != 0)
    def _scan():
        q = q_ref[...]
        b = bank_ref[...]
        s = jax.lax.dot_general(q, b, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_EXACT)                   # (Qb, Nb)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + nb * block_n
        # (Qb, 1) == (1, Nb) broadcast: a hit survives only within its
        # namespace
        ok = (col < nvalid_ref[0]) & (qns_ref[...] == bns_ref[...])
        s = jnp.where(ok, s, NEG_INF)
        _merge_topk(scores_ref, idx_ref, s, col, k)


def _kernel_quant(nvalid_ref, q_ref, bank_ref, scale_ref, scores_ref,
                  idx_ref, *, block_n: int, k: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    q = q_ref[...]
    b = bank_ref[...]                                # (Nb, D) int8
    # fused dequant: q · (scale * b_i8) == scale * (q · b_i8) — contract the
    # int8 tile directly (f32 accumulate on the MXU), then scale the score
    # columns; the f32 bank tile is never materialized
    s = jax.lax.dot_general(q, b.astype(jnp.float32), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_EXACT)                       # (Qb, Nb)
    s = s * scale_ref[...]                           # (1, Nb) broadcast
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + nb * block_n
    s = jnp.where(col < nvalid_ref[0], s, NEG_INF)
    _merge_topk(scores_ref, idx_ref, s, col, k)


def _kernel_quant_masked(nvalid_ref, flags_ref, nxt_ref, q_ref, bank_ref,
                         scale_ref, qns_ref, bns_ref, scores_ref, idx_ref, *,
                         block_n: int, k: int):
    del nxt_ref                     # read by the index_maps only
    qb, nb = pl.program_id(0), pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    @pl.when(flags_ref[qb * pl.num_programs(1) + nb] != 0)
    def _scan():
        q = q_ref[...]
        b = bank_ref[...]                            # (Nb, D) int8
        s = jax.lax.dot_general(q, b.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_EXACT)                   # (Qb, Nb)
        s = s * scale_ref[...]                       # fused dequant
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + nb * block_n
        ok = (col < nvalid_ref[0]) & (qns_ref[...] == bns_ref[...])
        s = jnp.where(ok, s, NEG_INF)
        _merge_topk(scores_ref, idx_ref, s, col, k)


def _tiles(Q: int, N: int, block_q: int, block_n: int):
    """(query tile, bank block, padded Q, padded N) of a launch."""
    bq = min(block_q, max(8, Q))
    bn = min(block_n, max(8, N))
    return bq, bn, -(-Q // bq) * bq, -(-N // bn) * bn


def grid_blocks(Q: int, N: int, block_q: int = 128,
                block_n: int = 512) -> int:
    """Query tiles times bank blocks of one launch over Q queries and an
    N-row bank: the steps a launch could scan."""
    bq, bn, Qp, Np = _tiles(Q, N, block_q, block_n)
    return (Qp // bq) * (Np // bn)


def block_flags(qns, bns, nv, bq: int, bn: int):
    """Which bank blocks each query tile can match, and where to fetch.

    `qns` (Qp,) and `bns` (Np,) are the padded labels, `nv` (1,) the live
    prefix.  Returns flat (tiles * blocks,) i32 `flags` (1 where block j
    holds a row < nv whose label is >= 0 and equals some query label of
    tile i) and `nxt` (the next flagged block at or after j in the tile,
    else its last flagged block, else 0).  One reduce over a broadcast
    compare: no (queries x rows) temporary is materialised."""
    nq, nb = qns.shape[0] // bq, bns.shape[0] // bn
    row = jnp.arange(bns.shape[0], dtype=jnp.int32)
    # dead, padded and out-of-prefix rows get -2, which no query label
    # takes (queries are >= 0, padded queries -1)
    live = jnp.where((bns >= 0) & (row < nv[0]), bns, -2)
    hit = live.reshape(1, 1, nb, bn) == qns.reshape(nq, bq, 1, 1)
    flags = jnp.any(hit, axis=(1, 3))                           # (nq, nb)
    j = jnp.arange(nb, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(flags, j, nb), axis=1, reverse=True)
    last = jnp.max(jnp.where(flags, j, 0), axis=1, keepdims=True)
    nxt = jnp.where(nxt == nb, last, nxt)
    return flags.astype(jnp.int32).reshape(-1), nxt.reshape(-1)


def topk_mips(queries, bank, k: int = 32, *, n_valid=None, q_ns=None,
              bank_ns=None, scales=None, block_q: int = 128,
              block_n: int = 512, interpret: bool = False):
    """queries (Q, D) · bank (N, D) -> (scores (Q, k) f32, indices (Q, k) i32).

    `n_valid` (traced i32 scalar, default N) bounds the live bank prefix:
    rows >= n_valid never appear (NEG_INF score, index -1 if nothing live
    fills the slot).  Passing a capacity-padded bank plus a traced n_valid
    keeps the compiled executable stable while the bank grows.

    Optional namespace mask: q_ns (Q,) i32 and bank_ns (N,) i32 (both or
    neither).  Bank rows whose namespace differs from the query's score
    NEG_INF and keep index -1 if nothing in-namespace fills the slot; q_ns
    must be >= 0, bank_ns == -1 marks tombstoned rows.  Masked launches
    skip the blocks no query of a tile can match (module docstring).

    Quantized bank (`scales`): pass an int8 bank plus per-row f32 scales
    (N,) — scores are computed against `scale * row_i8` with dequant fused
    into the block loop (f32 accumulation, see module docstring).  All other
    contracts (n_valid, namespace mask, -1 sentinels) are unchanged."""
    scores, idx, _ = topk_mips_counted(
        queries, bank, k, n_valid=n_valid, q_ns=q_ns, bank_ns=bank_ns,
        scales=scales, block_q=block_q, block_n=block_n, interpret=interpret)
    return scores, idx


def topk_mips_counted(queries, bank, k: int = 32, *, n_valid=None,
                      q_ns=None, bank_ns=None, scales=None,
                      block_q: int = 128, block_n: int = 512,
                      interpret: bool = False):
    """`topk_mips` plus the number of (query tile, bank block) steps the
    launch scanned, as a traced i32 scalar: the summed flags of a masked
    launch, `grid_blocks(Q, N)` of an unmasked one."""
    Q, D = queries.shape
    N = bank.shape[0]
    if n_valid is None:
        n_valid = N
    if scales is not None and bank.dtype != jnp.int8:
        raise TypeError(f"scales given but bank dtype is {bank.dtype}, "
                        "expected int8")
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1)
    bq, bn, Qp, Np = _tiles(Q, N, block_q, block_n)
    qp = jnp.pad(queries, ((0, Qp - Q), (0, 0)))
    bp = jnp.pad(bank, ((0, Np - N), (0, 0)))

    grid = (Qp // bq, Np // bn)
    nv_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs = [
        pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Qp, k), jnp.float32),
        jax.ShapeDtypeStruct((Qp, k), jnp.int32),
    ]
    q_spec = pl.BlockSpec((bq, D), lambda i, j: (i, 0))
    bank_spec = pl.BlockSpec((bn, D), lambda i, j: (j, 0))
    # per-row scales ride as a (1, Np) row, tiled with the bank blocks
    scale_args, scale_specs = (), ()
    if scales is not None:
        sp = jnp.pad(jnp.asarray(scales, jnp.float32),
                     (0, Np - N)).reshape(1, Np)
        scale_args = (sp,)
        scale_specs = (pl.BlockSpec((1, bn), lambda i, j: (0, j)),)
    if q_ns is None and bank_ns is None:
        body = _kernel_quant if scales is not None else _kernel
        scores, idx = pl.pallas_call(
            functools.partial(body, block_n=bn, k=k),
            grid=grid,
            in_specs=[nv_spec, q_spec, bank_spec, *scale_specs],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(nv, qp, bp, *scale_args)
        return scores[:Q], idx[:Q], jnp.int32(grid[0] * grid[1])
    assert q_ns is not None and bank_ns is not None, \
        "q_ns and bank_ns must be given together"
    # namespace ids ride along as 2-D blocks: (Qp, 1) column / (1, Np) row
    qns = jnp.pad(jnp.asarray(q_ns, jnp.int32), (0, Qp - Q),
                  constant_values=-1)
    bns = jnp.pad(jnp.asarray(bank_ns, jnp.int32), (0, Np - N),
                  constant_values=-2)
    flags, nxt = block_flags(qns, bns, nv, bq, bn)
    nb = grid[1]

    # scalar-prefetch refs (nv, flags, nxt) follow the grid indices; the
    # bank-side blocks go to the next flagged block, so skipped steps
    # repeat one block index and fetch nothing
    def nxt_block(i, j, nv_ref, flags_ref, nxt_ref):
        return nxt_ref[i * nb + j]

    def bank_rows(i, j, *refs):
        return (nxt_block(i, j, *refs), 0)

    def row_vec(i, j, *refs):
        return (0, nxt_block(i, j, *refs))

    def tile(i, j, *_):
        return (i, 0)

    if scales is not None:
        scale_specs = (pl.BlockSpec((1, bn), row_vec),)
    body = _kernel_quant_masked if scales is not None else _kernel_masked
    scores, idx = pl.pallas_call(
        functools.partial(body, block_n=bn, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bq, D), tile),
                pl.BlockSpec((bn, D), bank_rows),
                *scale_specs,
                pl.BlockSpec((bq, 1), tile),
                pl.BlockSpec((1, bn), row_vec),
            ],
            out_specs=[pl.BlockSpec((bq, k), tile),
                       pl.BlockSpec((bq, k), tile)],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(nv, flags, nxt, qp, bp, *scale_args, qns.reshape(Qp, 1),
      bns.reshape(1, Np))
    return scores[:Q], idx[:Q], jnp.sum(flags)
