"""CPU rehearsal of chip_smoke.py: its phases at a tiny size.

The phases are the chip smoke's own functions — the server as
`launch/serve.py` builds it, a bank filled through `apply_wal` plus
LoCoMo-shaped conversations recorded over HTTP, retrieval over HTTP on
localhost checked against the exact numpy MIPS, the scalar `rrf_fuse` and
`graph_expand_ref`, and the agent model against a plain forward — so the
reference comparisons guard every change, not only chip runs.  Only
`main()` demands a TPU."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

TINY = dict(bulk_rows=512, rows_per_tenant=64, conversations=2,
            noise_turns=20, host_demo=True)


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_serve_phase_matches_references(quantize):
    report, server, (context, question) = cs.phase_serve(quantize, **TINY)
    try:
        assert report["quantize"] == quantize
        assert report["live_rows"] > TINY["bulk_rows"]
        assert report["tokens_per_query"] > 0
        assert 0.0 <= report["accuracy"] <= 1.0
        assert context and question
    finally:
        server.frontend.close()
        server.service.close()


def test_agent_phase_matches_plain_forward():
    server = cs.build("none", seed=0, max_len=512, host_demo=True)
    server.frontend.start()
    try:
        out = cs.phase_agent(server, "[2023-05-01] (Nate; likes; sushi)",
                             "Which dish does Nate enjoy the most?")
        assert out["prompt_tokens"] > 0
        assert out["first_logits_max_abs_err"] <= 2e-3
    finally:
        server.frontend.close()
        server.service.close()


def test_reference_check_rejects_a_wrong_ranking():
    import numpy as np
    rng = np.random.default_rng(0)
    bank = rng.standard_normal((40, cs.D)).astype(np.float32)
    labels = np.zeros(40, np.int64)
    q = rng.standard_normal(cs.D).astype(np.float32)
    ref = cs.exact_mips(bank, labels, q, 0, 10)
    cs.check_dense(ref, ref, bank, labels, q, 0, "same")
    with pytest.raises(cs.SmokeFailure):
        cs.check_dense(ref[::-1], ref, bank, labels, q, 0, "reversed")
    with pytest.raises(cs.SmokeFailure):
        cs.check_scored([1, 2], [0.5, 0.4], [1, 2], [0.5, 0.3], "scores")


def test_main_refuses_a_host_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_sharded_phase_on_four_host_devices():
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, %r)
        import chip_smoke as cs
        out = cs.phase_sharded(4, rows_per_device=256, rows_per_tenant=32,
                               queries=8)
        assert out == {"rows": 1024, "per_device": 256}, out
        print("SHARDED_OK")
    """ % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "SHARDED_OK" in out.stdout, out.stderr[-2000:]


def test_compilation_cache_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.common.utils import init_compilation_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert init_compilation_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = init_compilation_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
