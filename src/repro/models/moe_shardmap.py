"""Manual expert-parallel MoE via shard_map — the §Perf replacement for the
pure-GSPMD sort-based dispatch.

Why: under pjit, the capacity-buffer scatter/gather with cross-shard indices
lowers to replicated-buffer masked all-reduces — measured at ~100 TB/device
per step for deepseek-v2 train_4k (EXPERIMENTS.md §Perf). The shard_map
formulation exploits the 2-D mesh structure instead:

  * activations are data-sharded and *replicated over the model axis* within
    each data row (they already are, post-attention);
  * every model rank owns E/model_size experts (w1/w2/w3 P("model",...));
  * each rank locally selects + buckets the tokens routed to ITS experts
    (x is replicated -> pure local gather, NO dispatch communication);
  * expert FFN on the local (E_loc, cap, d) buffer;
  * one psum over `model` combines the per-rank partial outputs.

Per-layer communication drops from O(E*cap*d) replicated-buffer reductions
to exactly one (T_loc, d) psum + the usual FSDP weight all-gathers (done
explicitly here with lax.all_gather so the traffic is identical to GSPMD's
FSDP handling).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..dist.sharding import current_rules


def _capacity(T: int, top_k: int, n_experts: int, factor: float) -> int:
    cap = int(math.ceil(T * top_k * factor / n_experts))
    return max(8, -(-cap // 8) * 8)


def moe_apply_shardmap(params, x: jnp.ndarray, *, n_experts: int, top_k: int,
                       capacity_factor: float = 1.25,
                       mlp_kind: str = "swiglu", router_norm: bool = True
                       ) -> Tuple[jnp.ndarray, dict]:
    """Drop-in for moe_apply when a mesh context is active (EP mode only:
    n_experts % model_size == 0). Falls back to local math on 1 device."""
    state = current_rules()
    mesh = state[0] if state else None
    if mesh is None or "model" not in mesh.shape:
        from .moe import moe_apply
        return moe_apply(params, x, n_experts=n_experts, top_k=top_k,
                         capacity_factor=capacity_factor, mlp_kind=mlp_kind)

    B, S, d = x.shape
    data_ax = "data"
    model_size = mesh.shape["model"]
    data_size = mesh.shape[data_ax]
    pod = "pod" in mesh.shape
    batch_axes = ("pod", "data") if pod else ("data",)
    assert n_experts % model_size == 0, (n_experts, model_size)
    E_loc = n_experts // model_size
    eff_data = data_size * (mesh.shape["pod"] if pod else 1)
    T_loc = (B // eff_data) * S
    cap_e = _capacity(T_loc, top_k, n_experts, capacity_factor)

    def body(router_w, w1, w3, w2, x_loc):
        # x_loc: (B_loc, S, d) — replicated over `model`
        # w*: FSDP-sharded over data on the d/ff dim -> gather explicitly
        w1f = jax.lax.all_gather(w1, data_ax, axis=1, tiled=True)
        w3f = jax.lax.all_gather(w3, data_ax, axis=1, tiled=True)
        w2f = jax.lax.all_gather(w2, data_ax, axis=2, tiled=True)
        my_rank = jax.lax.axis_index("model")

        xf = x_loc.reshape(-1, d)
        T = xf.shape[0]
        logits = xf.astype(jnp.float32) @ router_w            # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, top_k)               # (T, k)
        if router_norm:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        # local selection: assignments routed to MY experts
        flat_e = idx.reshape(-1)                              # (T*k,)
        flat_g = gate.reshape(-1)
        tok = jnp.arange(T * top_k) // top_k
        mine = (flat_e // E_loc) == my_rank
        e_loc = jnp.where(mine, flat_e % E_loc, E_loc)        # E_loc = drop
        order = jnp.argsort(e_loc)                            # mine first,
        sorted_e = e_loc[order]                               # grouped by e
        grp_start = jnp.searchsorted(sorted_e, jnp.arange(E_loc), "left")
        pos = jnp.arange(T * top_k) - grp_start[jnp.minimum(sorted_e,
                                                            E_loc - 1)]
        keep = (sorted_e < E_loc) & (pos < cap_e)
        dest = jnp.where(keep, sorted_e * cap_e + pos, E_loc * cap_e)

        buf = jnp.zeros((E_loc * cap_e + 1, d), x.dtype)
        buf = buf.at[dest].set(xf[tok[order]])                # local gather
        buf = buf[:-1].reshape(E_loc, cap_e, d)

        h1 = jnp.einsum("ecd,edf->ecf", buf, w1f)
        h3 = jnp.einsum("ecd,edf->ecf", buf, w3f)
        act = jax.nn.silu(h1) if mlp_kind == "swiglu" else jax.nn.gelu(h1)
        out_buf = jnp.einsum("ecf,efd->ecd", act * h3, w2f)

        flat_out = out_buf.reshape(E_loc * cap_e, d)
        gathered = jnp.where(
            keep[:, None],
            flat_out[jnp.minimum(dest, E_loc * cap_e - 1)], 0.0)
        weights = flat_g[order][:, None].astype(x.dtype)
        y = jnp.zeros((T, d), x.dtype).at[tok[order]].add(gathered * weights)
        # each token's k experts may live on other ranks: combine
        y = jax.lax.psum(y, "model")
        y = y.reshape(x_loc.shape)

        me = jnp.mean(probs, axis=0)
        one_hot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
        ce = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)
        lb = n_experts * jnp.sum(me * ce) / top_k
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        dropped = 1.0 - jnp.mean(keep.astype(jnp.float32)) * (
            T * top_k) / jnp.maximum(jnp.sum(mine.astype(jnp.float32)), 1.0)
        aux = {"lb_loss": lb, "z_loss": z,
               "dropped_frac": jnp.clip(dropped, 0.0, 1.0)}
        return y, aux

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model", "data", None), P("model", "data", None),
                  P("model", None, "data"),
                  P(batch_axes, None, None)),
        out_specs=(P(batch_axes, None, None),
                   {"lb_loss": P(), "z_loss": P(), "dropped_frac": P()}),
        check_vma=False)
    y, aux = fn(params["router"], params["w1"], params["w3"], params["w2"],
                x)
    if "shared" in params:
        from .layers import mlp_apply
        y = y + mlp_apply(params["shared"], x, mlp_kind)
    return y, aux
