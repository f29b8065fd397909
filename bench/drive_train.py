"""Training cells: the program's jitted train step fed by its own
prefetching input pipeline.

Set-up builds one object, the compiled step with its state (weights
from the seed, the program's AdamW state), and drives it through the
first `check_steps` steps with the window's own call and feed; the
readings the reference is compared with are taken on the way.  The
window then runs the same object for `--seconds`, syncing each step
with `block_until_ready`.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic, weights
from bench.tracing import Recorder


def _leaf_norms(tree, shapes) -> Dict[str, float]:
    """Per-layer norms of a tree in the program's layout, keyed like the
    reference's "name@layer"."""
    names = weights.leaf_names(shapes)

    @jax.jit
    def f(t):
        out = []
        for path, x in jax.tree_util.tree_flatten_with_path(t)[0]:
            x = x.astype(jnp.float32)
            axes = tuple(range(1, x.ndim))
            if weights._path(path).startswith(weights.STACK):
                out.append(jnp.sqrt(jnp.sum(x * x, axis=axes)))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x))[None])
        return jnp.concatenate(out)
    vals = np.asarray(f(tree))
    return {f"{n}@{l}": float(v) for (n, l, _), v in zip(names, vals)}


class TrainCell:
    def __init__(self, cfg: dict, mix: dict, seed: int) -> None:
        from repro.configs.base import ModelConfig, TrainConfig
        from repro.data.pipeline import SyntheticLMData
        from repro.models import build_model
        from repro.optim import adamw
        from repro.runtime.trainer import make_train_step
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.tcfg = TrainConfig(**cfg["train"])
        model = build_model(ModelConfig(**cfg["program"]))
        self.model = model
        self.shapes = jax.eval_shape(model.init, jax.random.key(0))
        params = weights.make_params(self.shapes, seed)
        self.state = {"params": params, "opt": adamw.init_state(params)}
        self.table = model.table()
        self.step_fn = jax.jit(make_train_step(model, self.tcfg),
                               donate_argnums=(0,))
        self.data = SyntheticLMData(model.cfg, mix["batch"], mix["seq_len"],
                                    seed=traffic.train_seed(seed),
                                    prefetch=mix["prefetch"])
        self.tokens_per_step = mix["batch"] * mix["seq_len"]
        self.rec = Recorder(False)

    def _step(self):
        with self.rec.span("bench.data_fetch"):
            batch = next(self.data)
        self.state, metrics, self.table = self.step_fn(self.state, batch,
                                                       self.table)
        return batch, metrics

    def first_steps(self) -> dict:
        """Steps 1..check_steps through the window's call and feed: the
        loss of each, the gradient as the optimizer got it in step 1
        (its first moment over 1 - b1), and each leaf's change after
        the last, with copies of the batches for the reference."""
        self.data.start(at_step=0)
        b1 = self.tcfg.b1
        losses, batches, grad = [], [], None
        for i in range(self.mix["check_steps"]):
            batch, metrics = self._step()
            batches.append({k: np.array(v) for k, v in batch.items()})
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad = {k: v / (1.0 - b1) for k, v in
                        _leaf_norms(self.state["opt"]["mu"],
                                    self.shapes).items()}
        init = weights.make_params(self.shapes, self.seed)
        change = jax.jit(lambda m, p: jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), m, p))(
                self.state["opt"]["master"], init)
        del init
        out = {"losses": losses, "grad": grad,
               "change": _leaf_norms(change, self.shapes),
               "batches": batches}
        del change
        return out

    def window(self, seconds: float, rec: Recorder) -> dict:
        """Steps until `seconds` have passed; the rate is over all of
        them and all of their time."""
        self.rec = rec
        t0 = time.monotonic()
        losses = []
        while True:
            el = time.monotonic() - t0
            if el >= seconds:
                break
            rec.tick(el)
            _, metrics = rec.step(self._timed_step, label="bench.train_step")
            losses.append(metrics["loss"])
        t_end = time.monotonic()
        rec.stop()
        finite = all(np.isfinite(float(x)) for x in losses)
        return {"t0": t0, "t_end": t_end, "steps": len(losses),
                "finite": finite}

    def _timed_step(self):
        batch, metrics = self._step()
        jax.block_until_ready(metrics["loss"])
        return batch, metrics

    def close(self) -> None:
        self.data.stop()
        self.state = None
