"""Work of the SAE's training step, counted from its shapes.

FLOPs per training sample of forward and backward: each of the four
dense layers costs 2 * d_in * d_out in the forward pass, the same again
for its weight gradient, and the same again for its input gradient, which
the first layer does not need (its input is data). Bias, activation and
loss terms are left out.

Bytes of one projected update, the least any implementation moves: read
the param, grad and both Adam moments of every leaf and write the param
and both moments, in float32.
"""
from __future__ import annotations


def _layers(cfg):
    d, h, k = cfg["n_features"], cfg["n_hidden"], cfg["n_classes"]
    return [(d, h), (h, k), (k, h), (h, d)]


def n_params(cfg) -> int:
    return sum(a * b + b for a, b in _layers(cfg))


def train_flops_per_unit(cfg, traffic) -> float:
    layers = _layers(cfg)
    fwd = sum(2 * a * b for a, b in layers)
    d_in_first = 2 * layers[0][0] * layers[0][1]
    return 3 * fwd - d_in_first


def update_bytes(cfg, traffic) -> float:
    return 7 * 4 * n_params(cfg)
