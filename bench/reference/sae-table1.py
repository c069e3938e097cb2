"""Plain reference of the paper's supervised autoencoder (arXiv:2307.09836
§5, Fig. 4) and of its projected training step (Algorithm 3, descent 1).

Net: x -> relu(x W1 + b1) -> z = h W2 + b2 (class logits) -> relu(z W3 +
b3) -> xhat = h' W4 + b4. Loss: lam * mean Huber(xhat - x, delta) + mean
cross-entropy of z. Weights: He normal (std sqrt(2 / fan_in)), zero
biases, drawn from the fit's key split four ways, one per layer, each
split again for (weight, bias). Step: gradient, Adam with global-norm
clipping, then the exact projection of the constrained leaf onto its
l1,inf ball. Batches: the fit's permutation of the training rows, taken
in order.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bench import plain

LAYERS = (("enc1", "n_features", "n_hidden"), ("enc2", "n_hidden", "n_classes"),
          ("dec1", "n_classes", "n_hidden"), ("dec2", "n_hidden", "n_features"))


def init(fit_seed: int, cfg: dict, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(fit_seed), 4)
    params = {}
    for k, (name, d_in, d_out) in zip(keys, LAYERS):
        wk, _ = jax.random.split(k)
        n_in, n_out = cfg[d_in], cfg[d_out]
        w = jax.random.normal(wk, (n_in, n_out)) * jnp.sqrt(2.0 / n_in)
        params[name] = {"w": w.astype(dtype), "b": jnp.zeros((n_out,), dtype)}
    return params


def loss(params, x, y, cfg: dict):
    h = jax.nn.relu(x @ params["enc1"]["w"] + params["enc1"]["b"])
    z = h @ params["enc2"]["w"] + params["enc2"]["b"]
    hd = jax.nn.relu(z @ params["dec1"]["w"] + params["dec1"]["b"])
    xhat = hd @ params["dec2"]["w"] + params["dec2"]["b"]
    err = jnp.abs(xhat - x)
    delta = cfg["huber_delta"]
    huber = jnp.where(err <= delta, 0.5 * err * err, delta * (err - 0.5 * delta))
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
    return cfg["lam"] * jnp.mean(huber) + ce


def readings(cfg: dict, traffic: dict, fit_seed: int, X_train, y_train,
             steps: int, dtype=jnp.float32) -> dict:
    """The check's readings (bench/check.py) of the first ``steps`` steps
    of the fit seeded ``fit_seed``, computed in ``dtype``."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _readings(cfg, traffic, fit_seed, X_train, y_train, steps,
                         dtype)


def _readings(cfg, traffic, fit_seed, X_train, y_train, steps, dtype):
    params0 = init(fit_seed, cfg, dtype)
    perm = np.random.default_rng(fit_seed).permutation(len(X_train))
    bs = cfg["batch_size"]
    batches = [perm[i * bs:(i + 1) * bs] for i in range(steps)]
    X = jnp.asarray(X_train, dtype)
    Y = jnp.asarray(y_train)
    leaf = traffic["pattern"].split("/")

    @jax.jit
    def step(params, m, v, t, idx):
        lval, grads = jax.value_and_grad(loss)(params, X[idx], Y[idx], cfg)
        params, m, v, g = plain.adam_step(params, grads, m, v, t, cfg["lr"])
        w = params[leaf[0]][leaf[1]]
        params[leaf[0]][leaf[1]] = plain.project_l1inf(
            w, traffic["radius"], traffic["axis"])
        return params, m, v, lval, g

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)
    params, m, v = params0, zeros, zeros
    losses, first_grad = [], None
    for t, idx in enumerate(batches, start=1):
        params, m, v, lval, g = step(params, m, v, jnp.float32(t),
                                     jnp.asarray(idx))
        losses.append(float(lval))
        if first_grad is None:
            first_grad = plain.norms(g)
    change = jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32)
                                    - b.astype(jnp.float32), params, params0)
    return {"loss": losses, "grad": first_grad, "change": plain.norms(change),
            "proj": {traffic["pattern"]: plain.norms(params)[traffic["pattern"]]}}
