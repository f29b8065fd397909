"""GPipe pipeline (parallel/pipeline.py): output and gradient equivalence
with the sequential stage composition, on a 4-stage subprocess mesh."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import (bubble_fraction, gpipe_apply,
                                         split_stages)

    mesh = make_mesh((4,), ("stage",))
    S, M, B, D = 4, 6, 2, 16
    rng = np.random.default_rng(0)
    # 8 layers -> 4 stages x 2 layers; each layer: x -> tanh(x @ w)
    layer_w = jnp.asarray(rng.standard_normal((8, D, D)) * 0.3, jnp.float32)
    mbs = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)

    def stage_fn(w_stack, x):          # w_stack: [2, D, D]
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, w_stack)
        return y

    stages = split_stages({"w": layer_w}, 4)

    def pipelined(w8, mbs):
        st = split_stages({"w": w8}, 4)
        return gpipe_apply(lambda p, x: stage_fn(p["w"], x), st, mbs, mesh)

    def sequential(w8, mbs):
        def per_mb(x):
            return stage_fn(w8, x)
        return jax.vmap(per_mb)(mbs)

    y_pipe = jax.jit(pipelined)(layer_w, mbs)
    y_seq = sequential(layer_w, mbs)
    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq),
                               atol=1e-5, rtol=1e-5)

    # gradient THROUGH the pipeline (scan + ppermute are differentiable)
    g_pipe = jax.grad(lambda w: jnp.sum(jnp.sin(pipelined(w, mbs))))(layer_w)
    g_seq = jax.grad(lambda w: jnp.sum(jnp.sin(sequential(w, mbs))))(layer_w)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               atol=1e-4, rtol=1e-4)

    assert abs(bubble_fraction(4, 6) - 3 / 9) < 1e-9
    print("OK")
""")


@pytest.mark.slow
def test_gpipe_equivalence_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=400,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2500:]
    assert "OK" in proc.stdout
