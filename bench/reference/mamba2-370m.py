"""Plain reference of Mamba-2 (arXiv:2405.21060) training steps.

Model, from the paper's description, in the configuration the program
runs (one B/C group, no conv bias, the gated norm's eps the
configuration's ``gated_norm_eps``, the other norms' ``arch.norm_eps``):

  x = embed[tokens]
  for each layer:  x = x + mixer(rmsnorm(x))
  logits = rmsnorm(x) @ embed[:vocab].T           (tied head)
  loss = mean cross-entropy of the next token

  mixer(u): z = u Wz, xs = u Wx, B = u WB, C = u WC, dt = u Wdt
            xs, B, C = silu(causal depthwise conv, width 4, of each)
            dt = softplus(dt + dt_bias); A = -exp(A_log)
            y = SSD(xs * dt, A * dt, B, C) + D * xs     (per head of 64)
            out = rmsnorm(y * silu(z)) Wo

SSD is the chunked form of the paper's minimal listing ("ssd_minimal"),
with chunks of 128 tokens and the mask applied before the exponential.
A training step is the gradient of the loss, Adam with global-norm
clipping at the warm-up learning rate, and every ``every_k`` optimizer
steps the exact projection of each layer's slice of a constrained leaf
onto its l1,inf ball. Weights come from ``bench/weights.py``, tokens from
``bench/data.py``, both from the seed. Each layer is rematerialised in the
backward pass so that the full model fits one chip.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from bench import data, plain, weights

CHUNK = 128
CONV = 4


def layout(cfg: dict, dtype=jnp.float32):
    """The tree of parameter shapes the reference reads."""
    a = cfg["arch"]
    L, d, N = a["n_layers"], a["d_model"], a["ssm_state"]
    di = a["ssm_expand"] * d
    H = di // a["ssm_headdim"]
    vp = -(-a["vocab"] // 128) * 128
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    ssm = {"wz": s(L, d, di), "wx": s(L, d, di), "wB": s(L, d, N),
           "wC": s(L, d, N), "wdt": s(L, d, H), "dt_bias": s(L, H),
           "A_log": s(L, H), "D": s(L, H), "conv_x": s(L, CONV, di),
           "conv_B": s(L, CONV, N), "conv_C": s(L, CONV, N),
           "norm": s(L, di), "wo": s(L, di, d)}
    return {"embed": {"table": s(vp, d)},
            "blocks": {"p0_ssm": {"ssm_norm": {"scale": s(L, d)}, "ssm": ssm}},
            "final_norm": {"scale": s(d,)}}


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def conv(x, w):
    """Causal depthwise conv: out_t = sum_k w[k] x_{t-3+k}, then silu."""
    T = x.shape[1]
    pad = jnp.pad(x, ((0, 0), (CONV - 1, 0), (0, 0)))
    return jax.nn.silu(sum(pad[:, k:k + T] * w[k] for k in range(CONV)))


def segsum(a):
    """(..., T) -> (..., T, T): sum of a[j+1..i] below the diagonal, -inf
    above it."""
    T = a.shape[-1]
    c = jnp.cumsum(a, -1)
    diff = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), diff, -jnp.inf)


def ssd(X, A, B, C):
    """X (b, l, h, p), A (b, l, h), B and C (b, l, n) -> Y (b, l, h, p)."""
    b, l, h, p = X.shape
    c = l // CHUNK
    X = X.reshape(b, c, CHUNK, h, p)
    B = B.reshape(b, c, CHUNK, -1)
    C = C.reshape(b, c, CHUNK, -1)
    A = jnp.moveaxis(A.reshape(b, c, CHUNK, h), 3, 1)          # (b, h, c, l)
    Acum = jnp.cumsum(A, -1)
    Lmat = jnp.exp(segsum(A))                                  # (b, h, c, l, s)
    G = jnp.einsum("bcln,bcsn->bcls", C, B)
    Y_diag = jnp.einsum("bhcls,bcls,bcshp->bclhp", Lmat, G, X)
    decay = jnp.exp(Acum[..., -1:] - Acum)                     # (b, h, c, l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", B, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(Acum[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(Acum))
    return (Y_diag + Y_off).reshape(b, l, h, p)


def mixer(p, u, headdim, gated_eps):
    b, l, _ = u.shape
    z, xs = u @ p["wz"], u @ p["wx"]
    Bm, Cm, dt = u @ p["wB"], u @ p["wC"], u @ p["wdt"]
    xs, Bm, Cm = conv(xs, p["conv_x"]), conv(Bm, p["conv_B"]), conv(Cm, p["conv_C"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    H = A.shape[0]
    xh = xs.reshape(b, l, H, headdim)
    y = ssd(xh * dt[..., None], A * dt, Bm, Cm) + p["D"][:, None] * xh
    y = y.reshape(b, l, H * headdim)
    return rmsnorm(y * jax.nn.silu(z), p["norm"], gated_eps) @ p["wo"]


def loss(params, tokens, labels, cfg: dict):
    a = cfg["arch"]
    eps = a["norm_eps"]
    x = params["embed"]["table"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = rmsnorm(x, p["ssm_norm"]["scale"], eps)
        return x + mixer(p["ssm"], h, a["ssm_headdim"],
                         cfg["gated_norm_eps"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["p0_ssm"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = x @ params["embed"]["table"][:a["vocab"]].T
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    take = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - take)


def _constrained(cfg):
    """[(leaf path, radius, axis, every_k)] of the configuration's balls."""
    paths = plain.leaf_paths(layout(cfg)).keys()
    return [(path, s["radius"], s["axis"], s["every_k"])
            for s in cfg["projection"] for path in paths
            if re.search(s["pattern"], path)]


def readings(cfg: dict, traffic: dict, seed: int, steps: int,
             dtype=jnp.float32) -> dict:
    """The check's readings (bench/check.py) of the first ``steps``
    training steps from the seed, computed in ``dtype``; the change is
    read after three steps."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _readings(cfg, traffic, seed, steps, dtype)


def train_step(cfg: dict, dtype=jnp.float32):
    """The jitted reference step: (params, m, v, t, lr, tokens, fired) ->
    (params, m, v, loss, {leaf: norm of its clipped grad}); ``fired`` (static)
    names the constrained leaves whose projection fires at this step."""
    balls = _constrained(cfg)

    def step(params, m, v, t, lr, tokens, fired):
        lval, grads = jax.value_and_grad(loss)(
            params, tokens[:, :-1], tokens[:, 1:], cfg)
        params, m, v, g = plain.adam_step(params, grads, m, v, t, lr)
        for path, radius, axis, _ in balls:
            if path in fired:
                w = plain.leaf_paths(params)[path]
                proj = jax.vmap(lambda s: plain.project_l1inf(
                    s, radius, axis))(w)
                params = _replace(params, path, proj)
        gn = {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
              for k, x in plain.leaf_paths(g).items()}
        return params, m, v, lval, gn

    return jax.jit(step, static_argnums=(6,), donate_argnums=(0, 1, 2))


def _readings(cfg, traffic, seed, steps, dtype):
    a = cfg["arch"]
    balls = _constrained(cfg)
    params = weights.init(layout(cfg), seed, jnp.float32)
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = train_step(cfg, dtype)
    losses, grad, change = [], None, None
    for t in range(1, steps + 1):
        tokens = jnp.asarray(data.token_rows(seed, t - 1, traffic["batch"],
                                             traffic["seq"], a["vocab"],
                                             traffic["zipf_a"]))
        lr = traffic["lr"] * min(1.0, t / traffic["warmup"])
        fired = tuple(path for path, *_, k in balls if t % k == 0)
        if t == 1:
            p0 = jax.tree_util.tree_map(jnp.copy, params)
        params, m, v, lval, gn = step(params, m, v, jnp.float32(t),
                                      jnp.float32(lr), tokens, fired)
        losses.append(float(lval))
        if t == 1:
            grad = {k: float(x) for k, x in gn.items()}
        if t == 3:
            change = plain.norms(jax.tree_util.tree_map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                params, p0))
            del p0
    flat = plain.norms(params)
    return {"loss": losses, "grad": grad, "change": change,
            "proj": {path: flat[path] for path, *_ in balls}}


def _replace(tree, path, value):
    keys = path.split("/")
    out = dict(tree)
    node = out
    for k in keys[:-1]:
        node[k] = dict(node[k])
        node = node[k]
    node[keys[-1]] = value
    return out
