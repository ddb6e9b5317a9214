"""Multi-device distribution tests. Device count is fixed at process start, so
these run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run8(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_query_engine_8dev_matches_single():
    out = _run8("""
        import numpy as np, jax
        from repro.data.synth_graph import *
        from repro.core.engine import GQFastDatabase, GQFastEngine
        schema = make_pubmed(n_docs=500, n_terms=50, n_authors=200)
        db = GQFastDatabase(schema, account_space=False)
        base = GQFastEngine(db)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        dist = GQFastEngine(db, mesh=mesh)
        for q, p in [(QUERY_AS, {"a0": 7}), (QUERY_AD, {"t1": 3, "t2": 9}),
                     (QUERY_FSD, {"d0": 5})]:
            assert np.allclose(base.query(q, **p), dist.query(q, **p),
                               rtol=1e-4, atol=1e-4)
        print("MATCH")
    """)
    assert "MATCH" in out


@pytest.mark.slow
def test_batched_distributed_query():
    out = _run8("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.data.synth_graph import *
        from repro.core.engine import GQFastDatabase, GQFastEngine
        from repro.core import executor as X
        from repro.core.planner import plan_query
        from repro.core.sql import parse
        schema = make_pubmed(n_docs=400, n_terms=40, n_authors=150)
        db = GQFastDatabase(schema, account_space=False)
        base = GQFastEngine(db)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        plan = plan_query(schema, parse(QUERY_AS))
        fb = X.compile_frontier_distributed(db.device, plan, mesh,
                                            ("data", "model"), batched=True)
        out = np.asarray(fb(jnp.arange(6)))
        expect = np.stack([base.query(QUERY_AS, a0=i) for i in range(6)])
        assert np.allclose(out, expect, rtol=1e-4, atol=1e-4)
        print("MATCH")
    """)
    assert "MATCH" in out


@pytest.mark.slow
def test_sharded_embedding_lookup_8dev():
    out = _run8("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models.embedding import sharded_embedding_lookup, mod_shard_table
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        V, D, ns = 1003, 16, 8
        tbl = rng.normal(size=(V, D)).astype(np.float32)
        sh = jnp.asarray(mod_shard_table(tbl, ns))
        ids = jnp.asarray(rng.integers(0, V, 64).astype(np.int32))
        sharded = jax.device_put(sh, jax.sharding.NamedSharding(mesh, P("model", None, None)))
        f = jax.jit(jax.shard_map(
            lambda t, i: sharded_embedding_lookup(t.reshape(-1, D), i, ns),
            mesh=mesh, in_specs=(P("model", None, None), P()), out_specs=P()))
        out = np.asarray(f(sharded, ids))
        assert np.allclose(out, tbl[np.asarray(ids)], atol=1e-5)
        print("MATCH")
    """)
    assert "MATCH" in out


@pytest.mark.slow
def test_compressed_psum_8dev():
    """EF int8 all-reduce across 8 devices ≈ exact mean; error-feedback keeps
    the long-run bias tiny."""
    out = _run8("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.dist.compression import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        gl = rng.normal(size=(8, 256)).astype(np.float32)  # per-device grads
        g_sh = jax.device_put(jnp.asarray(gl), jax.sharding.NamedSharding(mesh, P("data", None)))
        e0 = jax.device_put(jnp.zeros((8, 256)), jax.sharding.NamedSharding(mesh, P("data", None)))
        def body(g, e):
            m, er = compressed_psum(g[0], e[0], "data")
            return m, er[None]

        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P(), P("data", None))))

        mean, err = f(g_sh, e0)
        true = gl.mean(0)
        rel = np.abs(np.asarray(mean) - true).max() / np.abs(true).max()
        assert rel < 0.05, rel  # one-shot int8 tolerance
        # error feedback correctness: err == (g + 0) − dequant(local)
        mean2, err2 = f(g_sh, err)
        # over two steps the accumulated mean is closer to the exact sum
        two = np.asarray(mean) + np.asarray(mean2)
        rel2 = np.abs(two - 2 * true).max() / np.abs(2 * true).max()
        assert rel2 < rel, (rel2, rel)
        print("MATCH", rel, rel2)
    """)
    assert "MATCH" in out


def test_shard_hint_noop_without_mesh():
    from repro.models.common import shard_hint

    x = jnp.ones((4, 4))
    y = shard_hint(x, "data", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_spec_filtering_on_small_mesh():
    from repro.dist.sharding import _filter, lm_param_spec

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    # 'model' axis absent → dropped by the mesh filter; divisibility by the
    # 1-sized 'data' axis always holds
    spec = _filter(mesh, lm_param_spec("layers/wq", (2, 64, 4, 16), mesh, n_kv_heads=2))
    assert all(s is None or s == "data" for s in spec)
