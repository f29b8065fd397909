"""Context-parallel split-K decode: exactness vs the unsharded oracle.

Runs under a multi-device CPU mesh in a SUBPROCESS (the 8-device XLA flag
must be set before jax initializes; the main test process keeps 1 device)."""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.kernels import ref
    from repro.launch.mesh import make_mesh
    from repro.parallel.context import context_parallel_decode

    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, D = 2, 8, 4, 256, 32
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    mesh = make_mesh((4, 2), ("data", "model"))

    for pos in (S - 1, 100, 63):
        want = ref.decode_attention(
            q, k, v, kv_len=jnp.full((B,), pos + 1, jnp.int32))
        got = context_parallel_decode(q, k, v, jnp.int32(pos), mesh,
                                      context_axis="data",
                                      head_axis="model", impl="ref")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    # the wire win: ensure no big gathers — lower and count collective bytes
    from repro.core.hlo_analysis import analyze_module
    f = jax.jit(lambda q, k, v, p: context_parallel_decode(
        q, k, v, p, mesh, impl="ref"))
    mc = analyze_module(f.lower(q, k, v, jnp.int32(200)).compile().as_text(),
                        mesh_axes={"data": 4, "model": 2})
    kv_bytes = 2 * B * Hkv * S * D * 4
    assert mc.wire_bytes < kv_bytes / 4, (mc.wire_bytes, kv_bytes)
    print("OK", mc.wire_bytes)
""")


def test_context_parallel_decode_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
