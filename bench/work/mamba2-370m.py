"""Work of a Mamba-2 training step, counted from the configuration.

FLOPs per token of forward and backward, recomputation not counted: 6 x
the matmul parameters (wz, wx, wB, wC, wdt, wo of every layer and the
tied head over the true vocabulary), plus 3 x the SSD's own forward
multiply-adds x 2, per layer: within a chunk of Q tokens the causal half
of C B^T (Q N / 2) and of its product with x (Q H P / 2), and the chunk
states in and out (2 H P N).

Bytes of one projected update, the least any implementation moves: read
the param, grad and both Adam moments of every leaf and write the param
and both moments, in float32.
"""
from __future__ import annotations


def _sizes(cfg):
    a = cfg["arch"]
    d, N = a["d_model"], a["ssm_state"]
    di = a["ssm_expand"] * d
    H = di // a["ssm_headdim"]
    return a, d, N, di, H


def matmul_params(cfg) -> int:
    a, d, N, di, H = _sizes(cfg)
    per_layer = 2 * d * di + 2 * d * N + d * H + di * d
    return a["n_layers"] * per_layer + a["vocab"] * d


def n_params(cfg) -> int:
    a, d, N, di, H = _sizes(cfg)
    vp = -(-a["vocab"] // 128) * 128
    per_layer = (2 * d * di + 2 * d * N + d * H + 3 * H + 4 * (di + 2 * N)
                 + di + di * d + d)
    return a["n_layers"] * per_layer + vp * d + d


def train_flops_per_unit(cfg, traffic) -> float:
    a, d, N, di, H = _sizes(cfg)
    Q, P = a["ssm_chunk"], a["ssm_headdim"]
    ssd_macs = Q * N / 2 + Q * H * P / 2 + 2 * H * P * N
    return 6 * matmul_params(cfg) + 3 * 2 * ssd_macs * a["n_layers"]


def update_bytes(cfg, traffic) -> float:
    return 7 * 4 * n_params(cfg)
