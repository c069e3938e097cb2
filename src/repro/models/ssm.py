"""Mamba2 SSD (state-space duality) block — chunked training scan +
recurrent single-token decode. [arXiv:2405.21060]

Recurrence (per head h, head dim P, state dim N):
    h_t = exp(a_h dt_t) h_{t-1} + dt_t B_t x_t^T       (h_t in R^{P x N})
    y_t = h_t C_t + D_h x_t
Chunked form (Dao & Gu 2024): intra-chunk quadratic attention-like term +
inter-chunk recurrence over per-chunk states (lax.scan over chunks).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .param import PM
from .layers import rmsnorm_apply
from ..dist.sharding import shard

CONV_W = 4  # causal depthwise conv width


def ssm_layout(d: int, d_inner: int, n_state: int, headdim: int):
    H = d_inner // headdim
    return {
        "wz": PM((d, d_inner), ("fsdp", "mlp"), init="scaled"),
        "wx": PM((d, d_inner), ("fsdp", "mlp"), init="scaled"),
        "wB": PM((d, n_state), ("fsdp", None), init="scaled"),
        "wC": PM((d, n_state), ("fsdp", None), init="scaled"),
        "wdt": PM((d, H), ("fsdp", None), init="scaled"),
        "dt_bias": PM((H,), (None,), init="zeros"),
        "A_log": PM((H,), (None,), init="zeros"),
        "D": PM((H,), (None,), init="ones"),
        "conv_x": PM((CONV_W, d_inner), (None, "mlp"), init="scaled"),
        "conv_B": PM((CONV_W, n_state), (None, None), init="scaled"),
        "conv_C": PM((CONV_W, n_state), (None, None), init="scaled"),
        "norm": PM((d_inner,), (None,), init="ones"),
        "wo": PM((d_inner, d), ("mlp", "fsdp"), init="scaled"),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv, width CONV_W. x: (B, S, D); w: (CONV_W, D)."""
    pad = jnp.pad(x, ((0, 0), (CONV_W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1]] * w[i] for i in range(CONV_W))
    return jax.nn.silu(out)


def _causal_conv_step(x_new, tail, w):
    """x_new: (B, 1, D); tail: (B, CONV_W-1, D) previous inputs."""
    window = jnp.concatenate([tail, x_new], axis=1)       # (B, CONV_W, D)
    out = jnp.einsum("bwd,wd->bd", window, w)[:, None]
    return jax.nn.silu(out), window[:, 1:]


def _ssd_inputs(params, u):
    """u: (B, S, d) -> z, x (B,S,H,P), B/C (B,S,N), dt (B,S,H), a (H,)."""
    z = u @ params["wz"]
    x = u @ params["wx"]
    Bm = u @ params["wB"]
    Cm = u @ params["wC"]
    dt_raw = u @ params["wdt"]
    return z, x, Bm, Cm, dt_raw


def ssd_apply(params, u: jnp.ndarray, *, headdim: int, chunk: int = 64,
              tile_bf16: bool = False) -> jnp.ndarray:
    """Full-sequence chunked SSD. u: (B, S, d).

    tile_bf16: compute the quadratic intra-chunk tiles (L, G) in bf16 —
    halves the dominant HBM traffic; decay cumsums and the inter-chunk
    state scan stay f32 (§Perf lever)."""
    B_, S, d = u.shape
    z, x, Bm, Cm, dt_raw = _ssd_inputs(params, u)
    x = _causal_conv(x, params["conv_x"])
    Bm = _causal_conv(Bm, params["conv_B"])
    Cm = _causal_conv(Cm, params["conv_C"])
    x = shard(x, "batch", "seq", "mlp")

    H = params["A_log"].shape[0]
    P = headdim
    N = Bm.shape[-1]
    xh = x.reshape(B_, S, H, P).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B,S,H)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))              # (H,) < 0
    da = dt * a[None, None, :]                                     # (B,S,H)

    nc = S // chunk
    assert S % chunk == 0, (S, chunk)
    Q = chunk
    da_c = da.reshape(B_, nc, Q, H)
    dt_c = dt.reshape(B_, nc, Q, H)
    x_c = xh.reshape(B_, nc, Q, H, P)
    B_c = Bm.reshape(B_, nc, Q, N).astype(jnp.float32)
    C_c = Cm.reshape(B_, nc, Q, N).astype(jnp.float32)

    cum = jnp.cumsum(da_c, axis=2)                                 # (B,nc,Q,H)
    seg_total = cum[:, :, -1]                                      # (B,nc,H)

    # ---- intra-chunk (quadratic within chunk) ------------------------------
    # L[b,c,h,i,j] = exp(cum_i - cum_j) for i >= j else 0. Mask BEFORE the
    # exp: above the diagonal cum_i - cum_j > 0 grows with the chunk and
    # overflows exp to inf, and the masked inf turns into NaN gradients
    tdt = jnp.bfloat16 if tile_bf16 else jnp.float32
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]           # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(tri[None, None, :, :, None], diff,
                          -jnp.inf)).astype(tdt)
    G = jnp.einsum("bcin,bcjn->bcij", C_c.astype(tdt),
                   B_c.astype(tdt))                                # (B,nc,Q,Q)
    M = G[..., None] * L                                           # (B,nc,Q,Q,H)
    intra = jnp.einsum("bcijh,bcjh,bcjhp->bcihp", M, dt_c.astype(tdt),
                       x_c.astype(tdt)).astype(jnp.float32)

    # ---- chunk states + inter-chunk recurrence -----------------------------
    decay_to_end = jnp.exp(seg_total[:, :, None, :] - cum)         # (B,nc,Q,H)
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchpn",
                        B_c, dt_c * decay_to_end, x_c)

    def scan_chunks(h_prev, inp):
        st, seg = inp                                              # (B,H,P,N), (B,H)
        h_new = h_prev * jnp.exp(seg)[:, :, None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((B_, H, P, N), jnp.float32)
    _, h_before = jax.lax.scan(
        scan_chunks, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(seg_total, 1, 0)))
    h_before = jnp.moveaxis(h_before, 0, 1)                        # (B,nc,H,P,N)

    inter = jnp.einsum("bcin,bcih,bchpn->bcihp",
                       C_c, jnp.exp(cum), h_before)

    y = (intra + inter).reshape(B_, S, H, P)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] * xh
    y = y.reshape(B_, S, H * P).astype(u.dtype)

    # gated output norm (mamba2: RMSNorm(y * silu(z)))
    y = rmsnorm_apply({"scale": params["norm"]}, y * jax.nn.silu(z))
    return y @ params["wo"]


def ssm_init_cache(B: int, d_inner: int, n_state: int, headdim: int,
                   dtype=jnp.float32):
    H = d_inner // headdim
    return {
        "state": jnp.zeros((B, H, headdim, n_state), jnp.float32),
        "conv_x": jnp.zeros((B, CONV_W - 1, d_inner), dtype),
        "conv_B": jnp.zeros((B, CONV_W - 1, n_state), dtype),
        "conv_C": jnp.zeros((B, CONV_W - 1, n_state), dtype),
    }


def ssd_decode(params, u, cache, *, headdim: int):
    """Single-token recurrent step. u: (B, 1, d). Returns (y, new_cache)."""
    B_ = u.shape[0]
    z, x, Bm, Cm, dt_raw = _ssd_inputs(params, u)
    x, conv_x = _causal_conv_step(x, cache["conv_x"], params["conv_x"])
    Bm, conv_B = _causal_conv_step(Bm, cache["conv_B"], params["conv_B"])
    Cm, conv_C = _causal_conv_step(Cm, cache["conv_C"], params["conv_C"])

    H = params["A_log"].shape[0]
    P = headdim
    xh = x.reshape(B_, H, P).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B,H)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a[None, :])                               # (B,H)

    state = cache["state"]                                          # (B,H,P,N)
    state = (state * decay[:, :, None, None]
             + jnp.einsum("bh,bn,bhp->bhpn", dt, Bm[:, 0].astype(jnp.float32), xh))
    y = jnp.einsum("bhpn,bn->bhp", state, Cm[:, 0].astype(jnp.float32))
    y = y + params["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(B_, 1, H * P).astype(u.dtype)
    y = rmsnorm_apply({"scale": params["norm"]}, y * jax.nn.silu(z))
    y = y @ params["wo"]
    new_cache = {"state": state, "conv_x": conv_x, "conv_B": conv_B,
                 "conv_C": conv_C}
    return y, new_cache
