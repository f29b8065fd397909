"""GPipe-style pipeline parallelism over a 'stage' mesh axis (shard_map).

For models deeper than TP+DP can feed (or when a pod's ICI topology favors
ring neighbors), layers split into S stages; M microbatches stream through
with the classic GPipe schedule: at tick t, stage s processes microbatch
t - s. Mapped onto jax:

  * stage s's layer parameters live on the ranks of stage s
    (in_specs P('stage', ...) over a [S, ...] stacked stage-param tree);
  * activations hop stages via ONE collective-permute per tick (ring
    neighbor traffic — the cheapest link pattern on a torus);
  * the schedule is a lax.scan over T = M + S - 1 ticks; bubbles are the
    standard (S-1)/(M+S-1) fraction and show up in the XFA device fold as
    wasted ticks (the 'Wait' pseudo-component of pipelining).

This is the forward pipeline (serving / building block). Training composes
it with jax.grad through the scan+permute (both differentiable); the
equivalence test covers fwd and grad-through-pipeline on a 4-stage mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def gpipe_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                stage_params: Any, microbatches: jax.Array, mesh: Mesh,
                *, axis: str = "stage") -> jax.Array:
    """Run `microbatches` [M, B, ...] through S pipeline stages.

    stage_fn(params_s, x) -> x must be shape-preserving; stage_params is a
    pytree whose leaves are stacked [S, ...]. Returns [M, B, ...] outputs
    (microbatch i = stage_{S-1}(...stage_0(mb_i))).
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = microbatches.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(params_s, mbs):
        # params_s: this stage's params (leading stage dim stripped by
        # shard_map); mbs: [M, B, ...] (replicated across stages)
        s = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda a: a[0], params_s)
        zero = jnp.zeros_like(mbs[0])

        def tick(carry, t):
            cur = carry                       # activation arriving this tick
            idx = t - s                       # microbatch this stage handles
            active = jnp.logical_and(idx >= 0, idx < M)
            # stage 0 ingests a fresh microbatch; others take the carry
            inp = jnp.where(s == 0, mbs[jnp.clip(t, 0, M - 1)], cur)
            out = stage_fn(params_local, inp)
            out = jnp.where(active, out, inp)  # bubbles pass through
            with jax.named_scope("pipeline"):
                nxt = jax.lax.ppermute(out, axis, perm)
            # the LAST stage's outs are the pipeline's results
            return nxt, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(T))   # [T, B, ...]
        # microbatch i leaves the last stage at tick i + (S-1)
        results = outs[S - 1:]                              # [M, B, ...]
        return results[None]                                # stage dim back

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(axis),
        check_vma=False)
    stacked = fn(stage_params, microbatches)                # [S, M, B, ...]
    return stacked[-1]                                      # last stage's


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """The GPipe idle fraction — fed to the XFA 'Wait' attribution."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def split_stages(stacked_layer_params: Any, n_stages: int) -> Any:
    """[L, ...] stacked layer params -> [S, L/S, ...] per-stage stacks."""
    def re(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree.map(re, stacked_layer_params)
