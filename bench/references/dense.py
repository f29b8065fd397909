"""Plain float32 reference of the dense decoder family, as the
configuration files under bench/configs describe it.

Pre-norm blocks: RMSNorm with a scale; grouped-query attention with
rotary positions (half-split rotation, theta from the configuration),
causal, softmax scale head_dim**-0.5, query head j reading kv head
j // (heads / kv_heads); a two-matrix MLP with tanh-approximated GELU;
a final RMSNorm and an untied head.  Everything is float32 at `highest`
matmul precision, with no kernel, cache or batching of the program's.
Weights come from bench/weights.py by name and layer, as served
(bfloat16) and then widened.

`quant="fp8"` is the control, one precision step below the
configuration's bfloat16: every matrix product takes both operands in
float8 e4m3 (weights with a scale per output column, activations with
a scale per row), and the keys and values are rounded to e4m3 per row
as an fp8 cache would hold them; in training the activations'
gradients are rounded to e5m2 per row.  Accumulation and softmax stay
float32.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("norm1/scale", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "norm2/scale", "mlp/w_up", "mlp/w_down")
Q_BLOCK = 512


class Dims:
    def __init__(self, program: dict):
        p = program
        self.L, self.d, self.H, self.Hkv = (p["n_layers"], p["d_model"],
                                            p["n_heads"], p["n_kv_heads"])
        self.D, self.F, self.V = p["head_dim"], p["d_ff"], p["vocab"]
        self.theta, self.eps = float(p["rope_theta"]), float(p["norm_eps"])
        self.served = jnp.dtype(p["param_dtype"])

    def shape(self, name: str) -> Tuple[int, ...]:
        d, H, Hkv, D, F, V = self.d, self.H, self.Hkv, self.D, self.F, self.V
        return {"norm1/scale": (d,), "norm2/scale": (d,),
                "final_norm/scale": (d,),
                "attn/wq": (d, H * D), "attn/wk": (d, Hkv * D),
                "attn/wv": (d, Hkv * D), "attn/wo": (H * D, d),
                "mlp/w_up": (d, F), "mlp/w_down": (F, d),
                "embed/table": (V, d), "lm_head/w": (d, V)}[name]


def fp8(w: jax.Array, axis: int = 0) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along `axis` (0:
    per output column of a weight; -1: per row of an activation)."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_rows(x: jax.Array) -> jax.Array:
    """An activation rounded to e4m3 per row; its cotangent is rounded
    to e5m2 per row, as fp8 training keeps gradients."""
    return fp8(x, -1)


def _fp8_rows_fwd(x):
    return fp8(x, -1), None


def _fp8_rows_bwd(_, ct):
    s = jnp.max(jnp.abs(ct), axis=-1, keepdims=True) / 57344.0
    s = jnp.where(s > 0, s, 1.0)
    return ((ct / s).astype(jnp.float8_e5m2).astype(jnp.float32) * s,)


fp8_rows.defvjp(_fp8_rows_fwd, _fp8_rows_bwd)


def _same(x):
    return x


def _weight(key, dims: Dims, name: str, layer, quant: Optional[str]):
    w = W.leaf(key, name, layer, dims.shape(name), dims.served)
    w = w.astype(jnp.float32)
    if quant == "fp8" and w.ndim == 2:
        w = fp8(w)
    return w


def layer_weights(key, dims: Dims, layer, quant=None) -> Dict[str, jax.Array]:
    return {n: _weight(key, dims, n, layer, quant) for n in LAYER_LEAVES}


# -- the block ----------------------------------------------------------------
def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [S, H, D]; positions [S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs        # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, dims: Dims):
    """q [S, H, D], k/v [S, Hkv, D], causal; queries in blocks."""
    S = q.shape[0]
    g = dims.H // dims.Hkv
    kg = jnp.repeat(k, g, axis=1)                               # [S, H, D]
    vg = jnp.repeat(v, g, axis=1)
    scale = dims.D ** -0.5
    qb_len = min(Q_BLOCK, S)
    nb = S // qb_len

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qb_len, qb_len, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kg, precision=HI) * scale
        rows = i * qb_len + jnp.arange(qb_len)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vg, precision=HI)

    return jax.lax.map(block, jnp.arange(nb)).reshape(S, dims.H, dims.D)


def block(w, x, dims: Dims, act=_same):
    """One decoder layer on one sequence x [S, d]; `act` rounds every
    matrix-product input and the keys and values (the control)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    mm = lambda a, name: jnp.dot(act(a), w[name], precision=HI)
    h = rmsnorm(x, w["norm1/scale"], dims.eps)
    q = mm(h, "attn/wq").reshape(S, dims.H, dims.D)
    k = mm(h, "attn/wk").reshape(S, dims.Hkv, dims.D)
    v = mm(h, "attn/wv").reshape(S, dims.Hkv, dims.D)
    q, k = rope(q, pos, dims.theta), rope(k, pos, dims.theta)
    o = attention(q, act(k), act(v), dims).reshape(S, dims.H * dims.D)
    x = x + mm(o, "attn/wo")
    h = rmsnorm(x, w["norm2/scale"], dims.eps)
    u = jax.nn.gelu(mm(h, "mlp/w_up"), approximate=True)
    return x + mm(u, "mlp/w_down")


# -- serving: logits at the served positions ----------------------------------
def _bucket(n: int) -> int:
    """Padded length: a power of two of at least one query block, so a
    run compiles few reference programs (causal: pad rows change
    nothing before them)."""
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


def serve_gaps(program: dict, seed: int, items: Sequence[Tuple[np.ndarray,
               np.ndarray]], control: bool = False) -> dict:
    """items: (prompt ids, served ids) per request.  The reference reads
    each prompt with its served tokens and, at the position that
    produced each served token, how far that token's logit lies below
    its best logit.  With control=True the fp8 control runs beside it,
    and the gap of the token the control puts first is read too.

    Returns {"mean_gap": mean over the served tokens, "gap": widest,
    "gaps": [widest per request], "tokens": n} and, with the control,
    the same of the control's first tokens ("control_mean_gap",
    "control_gap", "control_gaps")."""
    dims = Dims(program)
    key = W.base_key(seed)
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in items]
    S = _bucket(max(len(s) for s in seqs))
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s

    with jax.default_matmul_precision("highest"):
        @jax.jit
        def embed(key, toks):
            t = W.leaf(key, "embed/table", -1, dims.shape("embed/table"),
                       dims.served).astype(jnp.float32)
            return jnp.take(t, toks, axis=0)

        @jax.jit
        def layer(key, l, x):
            w = layer_weights(key, dims, l)
            return jax.lax.map(lambda xs: block(w, xs, dims), x)

        @jax.jit
        def layer_q(key, l, x):
            w = layer_weights(key, dims, l, quant="fp8")
            return jax.lax.map(lambda xs: block(w, xs, dims, fp8_rows), x)

        @jax.jit
        def head(key, x, xc, rows, cols, served):
            """Gaps at the served positions (rows, cols) of the final
            hidden states x (reference) and xc (control)."""
            fn = W.leaf(key, "final_norm/scale", -1, (dims.d,),
                        dims.served).astype(jnp.float32)
            wh = W.leaf(key, "lm_head/w", -1, dims.shape("lm_head/w"),
                        dims.served).astype(jnp.float32)
            lg = jnp.dot(rmsnorm(x[rows, cols], fn, dims.eps), wh,
                         precision=HI)
            best = jnp.max(lg, -1)
            gap = best - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]
            hc = fp8_rows(rmsnorm(xc[rows, cols], fn, dims.eps))
            first = jnp.argmax(jnp.dot(hc, fp8(wh), precision=HI), -1)
            cgap = best - jnp.take_along_axis(lg, first[:, None], 1)[:, 0]
            return gap, cgap

        x = embed(key, jnp.asarray(toks))
        xc = x if control else None
        for l in range(dims.L):
            x = layer(key, l, x)
            if control:
                xc = layer_q(key, l, xc)
        rows, cols, served = [], [], []
        for i, (p, s) in enumerate(items):
            rows += [i] * len(s)
            cols += list(range(len(p) - 1, len(p) - 1 + len(s)))
            served += list(s)
        m = len(rows)
        pad = _bucket(m) - m           # few compiled head shapes
        idx = lambda a: jnp.asarray(np.asarray(a + [0] * pad, np.int32))
        g, cg = head(key, x, xc if control else x, idx(rows), idx(cols),
                     idx(served))
        g, cg = np.asarray(g)[:m], np.asarray(cg)[:m]
    ends = np.cumsum([len(s) for _, s in items])[:-1]
    gaps, cgaps = np.split(g, ends), np.split(cg, ends)
    out = {"mean_gap": float(g.mean()), "gap": float(g.max()),
           "gaps": [float(a.max()) for a in gaps], "tokens": m}
    if control:
        out["control_mean_gap"] = float(cg.mean())
        out["control_gap"] = float(cg.max())
        out["control_gaps"] = [float(a.max()) for a in cgaps]
    return out


# -- training: three AdamW steps ----------------------------------------------
def _decays(name: str) -> bool:
    return not name.endswith("scale")


def train_steps(program: dict, train: dict, seed: int,
                batches: List[Dict[str, np.ndarray]], quant=None) -> dict:
    """AdamW steps (warm-up then cosine learning rate, clipping by the
    global gradient norm, decoupled weight decay on matrices only) from
    the seed's weights over `batches`.  Returns the loss of each step,
    the first step's gradient per leaf as the optimizer applies it
    (after clipping), and each leaf's change after all the steps, as
    norms keyed "name@layer"."""
    dims = Dims(program)
    key = W.base_key(seed)
    names = [("embed/table", -1), ("lm_head/w", -1), ("final_norm/scale", -1)]
    names += [(n, l) for l in range(dims.L) for n in LAYER_LEAVES]
    label = lambda n, l: f"{n}@{l}"

    with jax.default_matmul_precision("highest"):
        @jax.jit
        def init(key):
            return {label(n, l): W.leaf(key, n, l, dims.shape(n),
                                        dims.served).astype(jnp.float32)
                    for n, l in names}

        def q(w):
            if quant == "fp8" and w.ndim == 2:
                return w + jax.lax.stop_gradient(fp8(w) - w)
            return w

        act = fp8_rows if quant == "fp8" else _same

        def row_loss(params, tokens, labels):
            x = jnp.take(params[label("embed/table", -1)], tokens, 0)
            for l in range(dims.L):
                w = {n: q(params[label(n, l)]) for n in LAYER_LEAVES}
                x = jax.checkpoint(lambda w, x: block(w, x, dims, act))(w, x)
            h = rmsnorm(x, params[label("final_norm/scale", -1)], dims.eps)
            lg = jnp.dot(act(h), q(params[label("lm_head/w", -1)]),
                         precision=HI)
            lse = jax.nn.logsumexp(lg, -1)
            gold = jnp.take_along_axis(lg, labels[:, None], 1)[:, 0]
            return jnp.mean(lse - gold)

        @jax.jit
        def grads(params, tokens, labels):
            """Mean loss and gradient over the rows, one row at a time."""
            n = tokens.shape[0]

            def body(acc, row):
                l, g = jax.value_and_grad(row_loss)(params, *row)
                return jax.tree.map(lambda a, b: a + b / n, acc,
                                    (l, g)), None
            zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
            (loss, g), _ = jax.lax.scan(body, zero, (tokens, labels))
            return loss, g

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adamw(params, mu, nu, g, step):
            b1, b2, eps = train["b1"], train["b2"], train["eps"]
            s = step.astype(jnp.float32)
            warm = train["warmup_steps"]
            prog = jnp.clip((s - warm) / max(train["total_steps"] - warm, 1),
                            0.0, 1.0)
            lr = train["learning_rate"] * jnp.where(
                s < warm, s / max(warm, 1),
                0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * prog)))
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            clip = jnp.minimum(1.0, train["grad_clip"] / (gn + 1e-9))
            out = {}
            for k in params:
                gk = g[k] * clip
                m = b1 * mu[k] + (1 - b1) * gk
                v = b2 * nu[k] + (1 - b2) * gk * gk
                upd = (m / (1 - b1 ** s)) / (jnp.sqrt(v / (1 - b2 ** s)) + eps)
                if _decays(k.split("@")[0]):
                    upd = upd + train["weight_decay"] * params[k]
                out[k] = (params[k] - lr * upd, m, v,
                          jnp.sqrt(jnp.sum(gk * gk)))
            pick = lambda i: {k: t[i] for k, t in out.items()}
            return pick(0), pick(1), pick(2), pick(3)

        diff_norms = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum((a[k] - b[k]) ** 2))
                                           for k in a})

        params = init(key)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, first_grad = [], None
        for i, b in enumerate(batches):
            loss, g = grads(params, jnp.asarray(b["tokens"]),
                            jnp.asarray(b["labels"]))
            params, mu, nu, applied = adamw(params, mu, nu, g,
                                            jnp.int32(i + 1))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {k: float(v) for k, v in applied.items()}
            del g
        del mu, nu
        change = {k: float(v)
                  for k, v in diff_norms(params, init(key)).items()}
    return {"losses": losses, "grad": first_grad, "change": change}
