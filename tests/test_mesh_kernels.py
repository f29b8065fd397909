"""Pallas kernels under a device mesh (kernels/ops.py `_per_device`): XLA
cannot partition a Mosaic kernel, so under a mesh each device runs it on
its own block through shard_map.  Loss and gradients of the smoke model
on a 2x2 (data, model) mesh must match the same step on one device and
the plain-jnp reference.  Kernels run in interpret mode on the CPU.

Runs in a subprocess (the device count must be set before jax init)."""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.parallel.axes import runtime_mesh

    cfg = get_smoke("tinyllama_1_1b")
    tok = jax.random.randint(jax.random.key(1), (4, 64), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok,
             "mask": jnp.ones_like(tok, jnp.float32)}
    mesh = make_mesh((2, 2), ("data", "model"))

    def loss_and_grads(impl, mesh):
        model = build_model(cfg, impl=impl)
        params = model.init(jax.random.key(0))
        loss_fn = lambda p: model.loss_fn(p, batch, model.table())[0]
        with runtime_mesh(mesh):
            loss, g = jax.jit(jax.value_and_grad(loss_fn))(params)
        return float(loss), [np.asarray(x, np.float32)
                             for x in jax.tree.leaves(g)]

    ref = loss_and_grads("ref", None)
    for mesh_ in (None, mesh):
        loss, grads = loss_and_grads("pallas", mesh_)
        assert abs(loss - ref[0]) < 1e-4, (mesh_, loss, ref[0])
        for a, b in zip(grads, ref[1]):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
    print("OK")
""")


def test_pallas_kernels_on_mesh_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK" in proc.stdout
