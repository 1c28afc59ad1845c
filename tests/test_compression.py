"""int8 error-feedback gradient compression: unit + multi-device parity."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")  # optional test dep; gate, don't fail collection
from hypothesis import given, settings, strategies as st

from repro.optim import compression as C


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_compress_decompress_bounded_error(seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal(257).astype(np.float32) * 10.0)
    e = jnp.zeros_like(g)
    q, s, resid = C.compress(g, e)
    assert q.dtype == jnp.int8
    deq = C.decompress(q, s)
    # quantization error bounded by half a step
    np.testing.assert_allclose(np.asarray(deq), np.asarray(g), atol=float(s) * 0.51)
    np.testing.assert_allclose(np.asarray(g - deq), np.asarray(resid), rtol=1e-5, atol=1e-6)


def test_error_feedback_corrects_bias():
    """With a CONSTANT gradient, error feedback must make the time-average
    of the dequantized stream converge to the true gradient."""
    g = jnp.asarray(np.linspace(-3, 3, 64).astype(np.float32) + 0.017)
    e = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    n = 50
    for _ in range(n):
        q, s, e = C.compress(g, e)
        acc = acc + C.decompress(q, s)
    avg = acc / n
    np.testing.assert_allclose(np.asarray(avg), np.asarray(g), atol=2e-3)


def test_residual_norm_stays_bounded():
    rng = np.random.default_rng(0)
    e = jnp.zeros(1024)
    norms = []
    for i in range(20):
        g = jnp.asarray(rng.standard_normal(1024).astype(np.float32))
        q, s, e = C.compress(g, e)
        norms.append(float(jnp.linalg.norm(e)))
    assert max(norms[5:]) < 10 * min(norms[5:]) + 1.0  # no blow-up


def test_compressed_psum_multidevice_parity():
    """8 virtual devices: compressed all-reduce ~= exact fp32 mean."""
    import subprocess, sys, os, textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim import compression as C

        mesh = jax.make_mesh(
            (8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
        )
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((8, 128)).astype(np.float32))

        def f(g):
            g = g[0]
            err = {"g": jnp.zeros_like(g)}
            avg, err = C.compressed_psum({"g": g}, err, "data")
            exact, _ = C.compressed_psum({"g": g}, err, "data", enabled=False)
            return avg["g"][None], exact["g"][None]

        avg, exact = jax.shard_map(
            f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False
        )(g)
        a, e = np.asarray(avg[0]), np.asarray(exact[0])
        rel = np.abs(a - e).max() / (np.abs(e).max() + 1e-9)
        assert rel < 0.02, rel
        print("PARITY_OK", rel)
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=300, cwd=os.getcwd(),
    )
    assert "PARITY_OK" in out.stdout, out.stdout + out.stderr
