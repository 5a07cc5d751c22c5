"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp ref.py oracles
(interpret mode on CPU — the kernel bodies execute exactly as written)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# topk_mips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_n,bank_n,dim,kk", [
    (1, 16, 8, 4),
    (7, 100, 32, 8),
    (33, 1000, 64, 16),
    (128, 513, 128, 32),     # non-divisible bank vs block
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_topk_mips_matches_oracle(q_n, bank_n, dim, kk, dtype):
    q = jax.random.normal(k(1), (q_n, dim)).astype(dtype)
    bank = jax.random.normal(k(2), (bank_n, dim)).astype(dtype)
    s, i = ops.topk_mips(q, bank, k=kk, block_q=32, block_n=64)
    sr, ir = ref.topk_mips_ref(q, bank, k=kk)
    assert i.shape == (q_n, kk) and s.shape == (q_n, kk)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("q_n,bank_n,dim,kk,n_ns", [
    (1, 16, 8, 4, 1),
    (7, 100, 32, 8, 3),
    (33, 513, 64, 16, 5),     # non-divisible bank vs block
    (9, 300, 16, 8, 40),      # multi-block bank, every ns owns < kk rows
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_topk_mips_masked_matches_oracle(q_n, bank_n, dim, kk, n_ns, dtype):
    q = jax.random.normal(k(21), (q_n, dim)).astype(dtype)
    bank = jax.random.normal(k(22), (bank_n, dim)).astype(dtype)
    q_ns = jnp.asarray(np.arange(q_n) % n_ns, jnp.int32)
    bank_ns = np.arange(bank_n) % n_ns
    bank_ns[::7] = -1                       # sprinkle tombstones
    bank_ns = jnp.asarray(bank_ns, jnp.int32)
    s, i = ops.topk_mips_masked(q, bank, q_ns, bank_ns, k=kk,
                                block_q=32, block_n=64)
    sr, ir = ref.topk_mips_masked_ref(q, bank, q_ns, bank_ns, k=kk)
    assert i.shape == (q_n, kk) and s.shape == (q_n, kk)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-3, atol=1e-3)
    # every returned hit stays inside its query's namespace
    bn = np.asarray(bank_ns)
    for r in range(q_n):
        for idx in np.asarray(i)[r]:
            if idx >= 0:
                assert bn[idx] == int(q_ns[r])


def test_topk_mips_masked_uniform_ns_equals_unmasked():
    """With every row in one namespace the mask is a no-op: the masked
    kernel must reproduce the unmasked kernel exactly."""
    q = jax.random.normal(k(23), (9, 16))
    bank = jax.random.normal(k(24), (77, 16))
    s0, i0 = ops.topk_mips(q, bank, k=8, block_q=8, block_n=16)
    s1, i1 = ops.topk_mips_masked(q, bank, jnp.zeros((9,), jnp.int32),
                                  jnp.zeros((77,), jnp.int32), k=8,
                                  block_q=8, block_n=16)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_topk_mips_masked_small_tenant_multiblock_emits_sentinels(dtype):
    """Regression: a tenant owning 0 < rows < k in a bank spanning several
    bank blocks must pad with -1 sentinels.  The old merge argmax'd over an
    all-NEG_INF row once in-namespace candidates ran out, re-emitting the
    index parked in running slot 0 at grid steps nb > 0 — ghost duplicates
    that pass downstream `i >= 0` filters and inflate RRF scores."""
    bank_n, kk = 1100, 8
    q = jax.random.normal(k(27), (4, 8)).astype(dtype)
    bank = jax.random.normal(k(28), (bank_n, 8)).astype(dtype)
    bank_ns = np.zeros((bank_n,), np.int32)
    bank_ns[[0, 40, 700]] = 1             # tenant 1 owns 3 of 1100 rows
    bank_ns = jnp.asarray(bank_ns)
    q_ns = jnp.asarray([1, 0, 1, 0], jnp.int32)
    # default block_n=512: three sequential bank blocks
    s, i = ops.topk_mips_masked(q, bank, q_ns, bank_ns, k=kk)
    sr, ir = ref.topk_mips_masked_ref(q, bank, q_ns, bank_ns, k=kk)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-3, atol=1e-3)
    i = np.asarray(i)
    for r in (0, 2):                      # tenant-1 queries: 3 hits then -1
        assert sorted(i[r][:3].tolist()) == [0, 40, 700]
        assert (i[r][3:] == -1).all()


def test_topk_mips_masked_empty_namespace_returns_sentinels():
    q = jax.random.normal(k(25), (2, 8))
    bank = jax.random.normal(k(26), (20, 8))
    q_ns = jnp.asarray([9, 0], jnp.int32)    # ns 9 owns no rows
    bank_ns = jnp.zeros((20,), jnp.int32)
    s, i = ops.topk_mips_masked(q, bank, q_ns, bank_ns, k=4,
                                block_q=8, block_n=8)
    assert (np.asarray(i)[0] == -1).all()
    assert (np.asarray(i)[1] >= 0).all()


@pytest.mark.parametrize("masked", [False, True])
def test_topk_mips_traced_n_valid_matches_truncated_oracle(masked):
    """Stable-shape contract: a capacity-padded bank + traced n_valid must
    answer exactly like the oracle on the truncated bank — for several
    n_valid values through ONE jitted executable (shapes never change)."""
    D, N_pad, kk = 16, 96, 6
    q = jax.random.normal(k(31), (5, D))
    bank = jax.random.normal(k(32), (N_pad, D))
    q_ns = jnp.asarray([0, 1, 2, 0, 1], jnp.int32)
    bank_ns = jnp.asarray(np.arange(N_pad) % 3, jnp.int32)
    for n_valid in (3, 17, 50, 96):
        if masked:
            s, i = ops.topk_mips_masked(q, bank, q_ns, bank_ns, k=kk,
                                        n_valid=n_valid,
                                        block_q=8, block_n=32)
            sr, ir = ref.topk_mips_masked_ref(q, bank[:n_valid], q_ns,
                                              bank_ns[:n_valid], k=kk) \
                if n_valid >= kk else ref.topk_mips_masked_ref(
                    q, bank, q_ns, bank_ns, k=kk, n_valid=n_valid)
        else:
            s, i = ops.topk_mips(q, bank, k=kk, n_valid=n_valid,
                                 block_q=8, block_n=32)
            sr, ir = ref.topk_mips_ref(q, bank, k=kk, n_valid=n_valid)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        mask = np.asarray(ir) >= 0
        # atol: f32 dot rounding is absolute (~D*eps*|q||b|), so a score
        # near zero carries it undiminished by any relative tolerance
        np.testing.assert_allclose(np.asarray(s)[mask], np.asarray(sr)[mask],
                                   rtol=1e-5, atol=1e-5)
        # returned hits always come from the live prefix
        ii = np.asarray(i)
        assert ((ii < n_valid) | (ii == -1)).all()


def test_topk_mips_n_valid_zero_returns_all_sentinels():
    q = jax.random.normal(k(33), (2, 8))
    bank = jax.random.normal(k(34), (32, 8))
    s, i = ops.topk_mips(q, bank, k=4, n_valid=0, block_q=8, block_n=8)
    assert (np.asarray(i) == -1).all()


def test_topk_scores_sorted_and_indices_valid():
    q = jax.random.normal(k(3), (9, 16))
    bank = jax.random.normal(k(4), (77, 16))
    s, i = ops.topk_mips(q, bank, k=8, block_q=8, block_n=16)
    s = np.asarray(s)
    assert (np.diff(s, axis=1) <= 1e-6).all(), "scores must be descending"
    assert ((np.asarray(i) >= 0) & (np.asarray(i) < 77)).all()


# 8 bank blocks of 32 rows, 2 query tiles of 8: (bank_ns, q_ns, n_valid)
_SKIP_N, _SKIP_BN, _SKIP_Q, _SKIP_BQ = 256, 32, 16, 8


def _skip_layout(case):
    r = np.arange(_SKIP_N)
    nv = _SKIP_N
    if case == "clustered_on_boundaries":      # one tenant per block
        bank_ns = r // 32
        q_ns = [0, 0, 2, 2, 0, 2, 0, 2, 5, 5, 6, 5, 6, 6, 5, 5]
    elif case == "clustered_across_boundaries":     # runs of 48 rows
        bank_ns = r // 48
        q_ns = [1, 3, 1, 3, 1, 1, 3, 3, 4, 4, 4, 2, 2, 4, 2, 4]
    elif case == "interleaved":               # every block flagged
        bank_ns = r % 5
        q_ns = list(np.arange(_SKIP_Q) % 5)
    elif case == "tombstone_block":
        bank_ns = r // 64
        bank_ns[64:96] = -1                   # block 2 all dead
        q_ns = [1, 1, 0, 1, 1, 1, 0, 1, 3, 1, 3, 3, 1, 3, 3, 1]
    elif case == "small_tenant_spread":       # 3 rows < k in blocks 0, 3, 7
        bank_ns = r // 32
        bank_ns[[3, 100, 230]] = 9
        q_ns = [9, 9, 4, 9, 9, 9, 4, 9, 9, 1, 9, 1, 9, 9, 1, 9]
    elif case == "n_valid_mid_block":
        bank_ns = r // 32
        nv = 150                              # block 4 holds rows 128..149
        q_ns = [4, 4, 0, 4, 0, 4, 4, 0, 6, 5, 6, 6, 5, 5, 6, 4]
    elif case == "empty_namespace":           # ns 42 owns no row
        bank_ns = r // 32
        q_ns = [42] * 8 + [42, 1, 42, 42, 1, 42, 42, 42]
    elif case == "tiles_differ":              # Q > block_q, disjoint sets
        bank_ns = r // 32
        q_ns = [0] * 8 + [7] * 8
    else:
        raise ValueError(case)
    return (np.asarray(bank_ns, np.int32), np.asarray(q_ns, np.int32), nv)


def _flagged_blocks(bank_ns, q_ns, nv):
    """Blocks holding a live row below nv that some query of the tile
    asks for, summed over tiles (worked out without the kernel)."""
    live = np.where(np.arange(_SKIP_N) < nv, bank_ns, -2)
    n = 0
    for t in range(_SKIP_Q // _SKIP_BQ):
        want = set(q_ns[t * _SKIP_BQ:(t + 1) * _SKIP_BQ].tolist())
        for b in range(_SKIP_N // _SKIP_BN):
            blk = live[b * _SKIP_BN:(b + 1) * _SKIP_BN]
            n += any(x >= 0 and x in want for x in blk.tolist())
    return n


@pytest.mark.parametrize("case", [
    "clustered_on_boundaries", "clustered_across_boundaries", "interleaved",
    "tombstone_block", "small_tenant_spread", "n_valid_mid_block",
    "empty_namespace", "tiles_differ"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_topk_mips_masked_block_skip_matches_oracle(case, dtype):
    """Masked launches scan only the blocks some query of a tile can match
    and still answer exactly like the oracle: ids equal, scores close, and
    the scanned count is the hand-worked count of flagged blocks."""
    from repro.kernels import topk_mips as tm
    bank_ns, q_ns, nv = _skip_layout(case)
    kk, D = 8, 16
    q = jax.random.normal(k(61), (_SKIP_Q, D))
    bank = jax.random.normal(k(62), (_SKIP_N, D))
    scales = None
    if dtype == "int8":
        bank, scales = ref.quantize_rows_ref(bank)
        sr, ir = ref.topk_mips_quant_masked_ref(q, bank, scales, q_ns,
                                                bank_ns, k=kk, n_valid=nv)
    else:
        q, bank = q.astype(dtype), bank.astype(dtype)
        sr, ir = ref.topk_mips_masked_ref(q, bank, q_ns, bank_ns, k=kk,
                                          n_valid=nv)
    search = jax.jit(lambda *a: tm.topk_mips_counted(
        *a[:2], kk, n_valid=a[2], q_ns=a[3], bank_ns=a[4], scales=a[5],
        block_q=_SKIP_BQ, block_n=_SKIP_BN, interpret=True))
    s, i, scanned = search(q, bank, jnp.int32(nv), jnp.asarray(q_ns),
                           jnp.asarray(bank_ns), scales)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    live = np.asarray(ir) >= 0
    np.testing.assert_allclose(np.asarray(s)[live], np.asarray(sr)[live],
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(s)[~live] == ref.NEG_INF).all()
    want = _flagged_blocks(bank_ns, q_ns, nv)
    assert int(scanned) == want
    total = tm.grid_blocks(_SKIP_Q, _SKIP_N, _SKIP_BQ, _SKIP_BN)
    assert total == 16
    assert want == total if case == "interleaved" else want < total


# ---------------------------------------------------------------------------
# topk_mips — quantized (int8 bank + per-row scales, fused dequant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_n,bank_n,dim,kk", [
    (1, 16, 8, 4),
    (7, 100, 32, 8),
    (33, 513, 64, 16),       # non-divisible bank vs block
])
def test_topk_mips_quant_matches_oracle(q_n, bank_n, dim, kk):
    q = jax.random.normal(k(41), (q_n, dim))
    bank = jax.random.normal(k(42), (bank_n, dim))
    codes, scales = ref.quantize_rows_ref(bank)
    s, i = ops.topk_mips_quant(q, codes, scales, k=kk,
                               block_q=32, block_n=64)
    sr, ir = ref.topk_mips_quant_ref(q, codes, scales, k=kk)
    assert i.shape == (q_n, kk) and s.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q_n,bank_n,dim,kk,n_ns", [
    (7, 100, 32, 8, 3),
    (9, 300, 16, 8, 40),     # multi-block bank, every ns owns < kk rows
])
def test_topk_mips_quant_masked_matches_oracle(q_n, bank_n, dim, kk, n_ns):
    q = jax.random.normal(k(43), (q_n, dim))
    bank = jax.random.normal(k(44), (bank_n, dim))
    codes, scales = ref.quantize_rows_ref(bank)
    q_ns = jnp.asarray(np.arange(q_n) % n_ns, jnp.int32)
    bank_ns = np.arange(bank_n) % n_ns
    bank_ns[::7] = -1                       # sprinkle tombstones
    bank_ns = jnp.asarray(bank_ns, jnp.int32)
    s, i = ops.topk_mips_quant_masked(q, codes, scales, q_ns, bank_ns,
                                      k=kk, block_q=32, block_n=64)
    sr, ir = ref.topk_mips_quant_masked_ref(q, codes, scales, q_ns,
                                            bank_ns, k=kk)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    bn = np.asarray(bank_ns)
    for r in range(q_n):
        for idx in np.asarray(i)[r]:
            if idx >= 0:
                assert bn[idx] == int(q_ns[r])


def test_topk_mips_quant_approximates_f32_search():
    """The fused dequant scan must track the f32 oracle: exact-match
    recall@k stays high and every dequantized score lands within the
    per-row quantization error bound of its true score."""
    D, N, kk = 32, 400, 10
    q = jax.random.normal(k(45), (6, D))
    bank = jax.random.normal(k(46), (N, D))
    codes, scales = ref.quantize_rows_ref(bank)
    _, i_f = ref.topk_mips_ref(q, bank, k=kk)
    s_q, i_q = ops.topk_mips_quant(q, codes, scales, k=kk,
                                   block_q=8, block_n=64)
    i_f, i_q, s_q = np.asarray(i_f), np.asarray(i_q), np.asarray(s_q)
    recall = np.mean([len(set(i_f[r]) & set(i_q[r])) / kk
                      for r in range(6)])
    assert recall >= 0.9, recall
    # |q·(scale*codes) - q·row| <= |q|_1 * scale/2 per row
    qn = np.abs(np.asarray(q)).sum(axis=1)
    sc = np.asarray(scales)
    true = np.asarray(q) @ np.asarray(bank).T
    for r in range(6):
        for j in range(kk):
            idx = i_q[r, j]
            bound = qn[r] * sc[idx] / 2 + 1e-4
            assert abs(s_q[r, j] - true[r, idx]) <= bound


def test_topk_mips_quant_traced_n_valid():
    """Quantized search keeps the stable-shape contract: several n_valid
    values through one executable, padded rows never surface."""
    D, N_pad, kk = 16, 96, 6
    q = jax.random.normal(k(47), (5, D))
    bank = jax.random.normal(k(48), (N_pad, D))
    codes, scales = ref.quantize_rows_ref(bank)
    for n_valid in (3, 17, 50, 96):
        s, i = ops.topk_mips_quant(q, codes, scales, k=kk, n_valid=n_valid,
                                   block_q=8, block_n=32)
        sr, ir = ref.topk_mips_quant_ref(q, codes, scales, k=kk,
                                         n_valid=n_valid)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        ii = np.asarray(i)
        assert ((ii < n_valid) | (ii == -1)).all()


def test_topk_mips_quant_rejects_f32_bank():
    q = jax.random.normal(k(49), (2, 8))
    bank = jax.random.normal(k(50), (16, 8))
    scales = jnp.ones((16,), jnp.float32)
    with pytest.raises(TypeError, match="int8"):
        ops.topk_mips_quant(q, bank, scales, k=4)


@pytest.mark.parametrize("variant", ["plain", "masked", "quant",
                                     "quant_masked"])
def test_topk_mips_empty_bank_n_valid_zero_all_sentinels(variant):
    """n_valid=0 (an index before its first append, or fully demoted):
    every variant must return all -1 indices, never garbage rows."""
    D, N, kk = 8, 32, 4
    q = jax.random.normal(k(51), (3, D))
    bank = jax.random.normal(k(52), (N, D))
    codes, scales = ref.quantize_rows_ref(bank)
    q_ns = jnp.zeros((3,), jnp.int32)
    bank_ns = jnp.zeros((N,), jnp.int32)
    if variant == "plain":
        s, i = ops.topk_mips(q, bank, k=kk, n_valid=0, block_q=8, block_n=8)
    elif variant == "masked":
        s, i = ops.topk_mips_masked(q, bank, q_ns, bank_ns, k=kk, n_valid=0,
                                    block_q=8, block_n=8)
    elif variant == "quant":
        s, i = ops.topk_mips_quant(q, codes, scales, k=kk, n_valid=0,
                                   block_q=8, block_n=8)
    else:
        s, i = ops.topk_mips_quant_masked(q, codes, scales, q_ns, bank_ns,
                                          k=kk, n_valid=0,
                                          block_q=8, block_n=8)
    assert (np.asarray(i) == -1).all()


def test_quantize_rows_ref_roundtrip_error_bound():
    """Per-element dequant error is bounded by scale/2; zero rows get
    scale 0 and reconstruct exactly."""
    rng = np.random.default_rng(0)
    bank = rng.standard_normal((64, 32)).astype(np.float32)
    bank[5] = 0.0
    bank[9] *= 1e-6                         # tiny-norm row
    bank[11] *= 1e4                         # huge-norm row
    codes, scales = ref.quantize_rows_ref(bank)
    codes, scales = np.asarray(codes), np.asarray(scales)
    assert codes.dtype == np.int8
    assert (np.abs(codes) <= 127).all()
    recon = codes.astype(np.float32) * scales[:, None]
    err = np.abs(recon - bank)
    assert (err <= scales[:, None] / 2 + 1e-7).all()
    assert scales[5] == 0.0 and (codes[5] == 0).all()
    assert (recon[5] == 0).all()


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,K,G,S,D,bq,bk", [
    (1, 1, 1, 32, 16, 8, 8),
    (2, 2, 4, 64, 32, 16, 32),
    (1, 3, 2, 70, 32, 32, 16),    # ragged vs blocks
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(B, K, G, S, D, bq, bk, dtype, causal):
    q = jax.random.normal(k(5), (B, K, G, S, D)).astype(dtype)
    kk = jax.random.normal(k(6), (B, K, S, D)).astype(dtype)
    vv = jax.random.normal(k(7), (B, K, S, D)).astype(dtype)
    out = ops.flash_attention(q, kk, vv, causal=causal, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, kk, vv, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_sliding_window():
    B, K, G, S, D = 1, 2, 2, 96, 16
    q = jax.random.normal(k(8), (B, K, G, S, D))
    kk = jax.random.normal(k(9), (B, K, S, D))
    vv = jax.random.normal(k(10), (B, K, S, D))
    out = ops.flash_attention(q, kk, vv, causal=True, window=16,
                              block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, kk, vv, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,K,G,T,D,bt", [
    (1, 1, 1, 64, 16, 16),
    (3, 2, 4, 200, 32, 64),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_attention_matches_oracle(B, K, G, T, D, bt, dtype):
    q = jax.random.normal(k(11), (B, K, G, D)).astype(dtype)
    kk = jax.random.normal(k(12), (B, K, T, D)).astype(dtype)
    vv = jax.random.normal(k(13), (B, K, T, D)).astype(dtype)
    kv_len = jnp.asarray([T - 3 - 7 * b for b in range(B)], jnp.int32)
    out = ops.decode_attention(q, kk, vv, kv_len, block_t=bt)
    want = ref.decode_attention_ref(q, kk, vv, kv_len)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_decode_attention_ragged_lengths_ignore_tail():
    """Cache contents past kv_len must not affect the output."""
    B, K, G, T, D = 2, 1, 2, 128, 16
    q = jax.random.normal(k(14), (B, K, G, D))
    kk = jax.random.normal(k(15), (B, K, T, D))
    vv = jax.random.normal(k(16), (B, K, T, D))
    kv_len = jnp.asarray([40, 90], jnp.int32)
    out1 = ops.decode_attention(q, kk, vv, kv_len, block_t=32)
    kk2 = kk.at[:, :, 100:].set(999.0)
    vv2 = vv.at[:, :, 100:].set(-999.0)
    out2 = ops.decode_attention(q, kk2, vv2, kv_len, block_t=32)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
