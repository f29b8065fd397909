"""Manual-TP MLP (parallel/tp.py): numerical equivalence vs the pjit path.

Runs under an 8-device CPU mesh in a subprocess (device count must be set
before jax init)."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.launch.mesh import make_mesh
    from repro.parallel.axes import runtime_mesh
    from repro.core.hlo_analysis import analyze_module

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(get_smoke("tinyllama_1_1b"), d_ff=256)
    tok = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok,
             "mask": jnp.ones_like(tok, jnp.float32)}
    outs = {}
    for manual in (False, True):
        c = dataclasses.replace(cfg, manual_tp=manual)
        model = build_model(c, impl="ref")
        params = model.init(jax.random.key(0))
        with runtime_mesh(mesh):
            loss_fn = lambda p: model.loss_fn(p, batch, model.table())[0]
            loss, g = jax.jit(jax.value_and_grad(loss_fn))(params)
            outs[manual] = (float(loss), jax.tree.map(np.asarray, g))
    l0, g0 = outs[False]
    l1, g1 = outs[True]
    assert abs(l0 - l1) < 1e-4, (l0, l1)
    errs = [float(np.max(np.abs(a.astype(np.float32)
                                - b.astype(np.float32))))
            for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1))]
    # grads agree to bf16-cotangent rounding (the ONLY numerics change)
    assert max(errs) < 2e-2, max(errs)

    # also check the gated (SwiGLU) path standalone
    from repro.parallel.tp import col_row_mlp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((32, 64)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((32, 64)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    with runtime_mesh(mesh):
        y_tp = jax.jit(lambda *a: col_row_mlp(a[0], a[1], a[3], a[2], True))(
            x, wu, wg, wd)
    h = jax.nn.silu(x @ wg) * (x @ wu)
    y_ref = h @ wd
    np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    print("OK")
""")


@pytest.mark.slow
def test_manual_tp_equivalence_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=400,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
