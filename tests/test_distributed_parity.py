"""Distributed retrieval parity: sharded_topk on a CPU mesh of fake host
devices must return exactly the single-device topk_mips / topk_mips_ref
results, including the k > shard_rows edge and the namespace-masked
multi-tenant path (local Pallas kernel per shard → all_gather → re-rank).
Runs in a subprocess so the main pytest process keeps its single CPU device
(same pattern as test_distribution.py)."""
import subprocess
import sys
import textwrap

import pytest


def _run_parity(code: str):
    # JAX_PLATFORMS=cpu keeps the child off the libtpu plugin probe: its
    # /tmp/libtpu_lockfile serializes against other jax processes (the
    # pytest parent / earlier subprocess tests) and can stall the child
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "PARITY_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_sharded_topk_parity_cpu_mesh():
    _run_parity(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.vector_index import sharded_topk
        from repro.kernels import ops, ref
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("data", "model"))   # 8 shards
        q = jax.random.normal(jax.random.PRNGKey(0), (5, 32))
        bank = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        # shard_rows = 64/8 = 8: k=6 fits in one shard, k=12 exceeds it;
        # the local top-k routes through the Pallas kernel (interpret mode)
        for k in (6, 12):
            for use_kernel in (True, False):
                with mesh:
                    s, i = sharded_topk(q, bank, k=k, mesh=mesh,
                                        use_kernel=use_kernel)
                sr, ir = ref.topk_mips_ref(q, bank, k=k)
                np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
                np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                           rtol=1e-5)
            sk, ik = ops.topk_mips(q, bank, k=k, block_q=8, block_n=16)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ik))
            np.testing.assert_allclose(np.asarray(s), np.asarray(sk),
                                       rtol=1e-4)
        print("PARITY_OK")
    """))


@pytest.mark.slow
def test_sharded_topk_masked_parity_cpu_mesh():
    """Namespace-masked sharded search == the single-device masked oracle,
    tombstones included, even when a tenant owns fewer than k rows and when
    k exceeds the per-shard row count."""
    _run_parity(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.vector_index import sharded_topk
        from repro.kernels import ops, ref
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("data", "model"))   # 8 shards of 8 rows
        q = jax.random.normal(jax.random.PRNGKey(0), (6, 32))
        bank = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        # ns 0/1/2 interleaved, ns 7 owns exactly 2 rows, ns 9 owns none,
        # and every 7th row is a tombstone
        bank_ns = np.arange(64) % 3
        bank_ns[[5, 33]] = 7
        bank_ns[::7] = -1
        bank_ns = jnp.asarray(bank_ns, jnp.int32)
        q_ns = jnp.asarray([0, 1, 2, 7, 9, 0], jnp.int32)
        for k in (6, 12):                 # 12 > shard_rows = 8
            for use_kernel in (True, False):
                with mesh:
                    s, i = sharded_topk(q, bank, k=k, mesh=mesh,
                                        q_ns=q_ns, bank_ns=bank_ns,
                                        use_kernel=use_kernel)
                sr, ir = ref.topk_mips_masked_ref(q, bank, q_ns, bank_ns, k=k)
                np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
                live = np.asarray(ir) >= 0
                # atol: f32 dot rounding is absolute (~D*eps*|q||b|)
                np.testing.assert_allclose(np.asarray(s)[live],
                                           np.asarray(sr)[live], rtol=1e-5,
                                           atol=1e-5)
            sk, ik = ops.topk_mips_masked(q, bank, q_ns, bank_ns, k=k,
                                          block_q=8, block_n=16)
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ik))
        print("PARITY_OK")
    """))
