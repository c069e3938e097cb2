"""Sharding-aware packed l1,inf projection (DESIGN.md §7).

The packed engine in ``core.constraints``/``core.engine`` concatenates every
l1,inf leaf into one (n_max, sum m) buffer. Single-device that is ideal; under
GSPMD it is a disaster: the concatenate forces every FSDP/TP-sharded weight to
be all-gathered into a replicated buffer each step. This module keeps the
math identical while keeping shards resident:

  * the packed buffer is laid out COLUMN-SHARDED over the whole mesh — each
    rank owns ``m / D`` columns of every entry (columns are independent
    sub-problems: sort, prefix sums, and the final clip never cross columns);
  * entering ``shard_map``, GSPMD moves each leaf from its parameter layout
    (e.g. FSDP rows over "data", TP columns over "model") to the canonical
    column shard — a balanced all-to-all of ``|leaf| / D`` bytes per rank,
    never a full-weight all-gather;
  * the segmented Newton runs on local blocks; the only cross-rank traffic
    per Eq.-(19) evaluation is one psum of a (num_segments,) f32 vector
    (``core.l1inf.project_l1inf_segmented_sharded``);
  * leaves whose column count the mesh does not divide FALL BACK to
    replication inside the body (their reduction contributions are masked
    to rank 0 so every column is counted exactly once) — that fallback IS
    a per-step gather of the leaf, so ``shard_packed_plan`` warns loudly;
    pad the projected dim to a device-count multiple to stay resident.

Theta (and hence the projected weights) match the gathered solve up to fp
reduction order.

Family dispatch (PR 4): the plan names its constraint family
(``core.families``) and the shard_map body runs that family's per-column
statistics — every hook is per-column given the shared theta, so plain,
weighted, masked, and bilevel sub-buffers all keep the one-psum-per-eval
contract; weight-aware families slice their per-column ``w_col`` vector
rank-locally (never communicated).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..core.constraints import (PackedPlan, _PackedEntry, _pack_entry,
                                _unpack_entry, _LANE)
from ..core.families import get_family, project_segmented_family_sharded

__all__ = ["ShardedPlan", "shard_packed_plan", "project_plan_sharded",
           "fused_plan_sharded"]


@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """Per-rank layout of one PackedPlan on a mesh (all fields static).

    ``local`` is a PackedPlan describing each rank's column block: entries
    keep their global rows/lead/segment ids but ``m``/``m_pad``/``col_start``
    are per-rank. ``col_sharded[i]`` says entry i's columns are split over
    the mesh (vs replicated on every rank and owned by rank 0).

    >>> sp = shard_packed_plan(plan, n_devices=8)   # sp: ShardedPlan
    """
    global_plan: PackedPlan
    local: PackedPlan
    col_sharded: Tuple[bool, ...]
    n_devices: int

    def owned_cols(self) -> np.ndarray:
        """Static part of the contribution mask: True for columns of
        column-sharded entries (every rank owns its slice); False for
        replicated entries' columns (ownership resolves to rank 0 at
        trace time) and for lane padding (invalid anyway)."""
        owned = np.zeros((self.local.total_cols,), bool)
        for e, sh in zip(self.local.entries, self.col_sharded):
            if sh:
                lo = e.col_start
                owned[lo: lo + e.lead * e.m_pad] = True
        return owned

    def virtual_owned_cols(self) -> np.ndarray:
        """Dense-layout twin of :meth:`owned_cols` for the fused step's
        VIRTUAL packing (no lane padding, entry order — see
        ``PackedPlan.virtual_seg_ids``): True on every column of a
        column-sharded entry, False on replicated entries' columns
        (resolved to rank 0 at trace time)."""
        parts = [np.full((e.lead * e.m,), sh, bool)
                 for e, sh in zip(self.local.entries, self.col_sharded)]
        return (np.concatenate(parts) if parts
                else np.zeros((0,), bool))


def shard_packed_plan(plan: PackedPlan, n_devices: int) -> ShardedPlan:
    """Split a packed plan column-wise over ``n_devices`` ranks.

    Entries whose column count is divisible by the device count get
    ``m / D`` columns per rank (lane-padded locally); the rest stay
    replicated. Pure shape bookkeeping — safe during tracing.

    >>> sp = shard_packed_plan(plan, n_devices=len(jax.devices()))
    """
    entries, flags, col = [], [], 0
    for e in plan.entries:
        sharded = n_devices > 1 and e.m % n_devices == 0
        if not sharded and n_devices > 1:
            # replication means GSPMD gathers this leaf at the shard_map
            # boundary every step — the cost the sharded engine exists to
            # avoid. Loud, because the caller can usually fix it by padding
            # the projected dim to a device-count multiple.
            warnings.warn(
                f"sharded projection: leaf {e.shape} has {e.m} columns, "
                f"not divisible by the {n_devices}-device mesh — this "
                f"entry is replicated (a per-step all-gather)",
                stacklevel=2)
        m_loc = e.m // n_devices if sharded else e.m
        m_pad = -(-m_loc // _LANE) * _LANE
        entries.append(dataclasses.replace(e, m=m_loc, m_pad=m_pad,
                                           col_start=col))
        flags.append(sharded)
        col += e.lead * m_pad
    local = PackedPlan(key=plan.key, every_k=plan.every_k, n_max=plan.n_max,
                       total_cols=col, num_segments=plan.num_segments,
                       entries=tuple(entries), family=plan.family)
    return ShardedPlan(global_plan=plan, local=local,
                       col_sharded=tuple(flags), n_devices=n_devices)


def _col_dim(e: _PackedEntry) -> int:
    """Index of the canonical COLUMN dim in the entry's original leaf shape
    (the trailing matrix dim, or the one before it when the spec's max axis
    selected the trailing dim)."""
    return len(e.shape) - 2 if e.transpose else len(e.shape) - 1


def _leaf_spec(e: _PackedEntry, sharded: bool,
               axis_names: Tuple[str, ...]) -> P:
    if not sharded:
        return P(*([None] * len(e.shape)))
    axes = [None] * len(e.shape)
    axes[_col_dim(e)] = axis_names if len(axis_names) > 1 else axis_names[0]
    return P(*axes)


def project_plan_sharded(leaves: Sequence[jnp.ndarray], plan: PackedPlan,
                         mesh: Mesh,
                         theta0: Optional[jnp.ndarray] = None,
                         max_iter: int = 32):
    """Project one packed plan's leaves, shards resident (shard_map).

    ``leaves`` are the plan entries' leaf arrays in entry order (any
    sharding — GSPMD reshards to the canonical column layout at the
    shard_map boundary, an all-to-all, not a gather); ``theta0``:
    optional (num_segments,) f32 warm start. Returns
    (projected_leaves list, theta (num_segments,) f32, iters int32) with
    theta/iters replicated; projected leaves keep their input shardings.

    >>> outs, theta, iters = project_plan_sharded(vals, plan, mesh)
    """
    axis_names = tuple(mesh.axis_names)
    D = int(np.prod([mesh.shape[a] for a in axis_names], dtype=np.int64))
    sp = shard_packed_plan(plan, D)
    sids = sp.local.seg_ids()
    C_seg = plan.radii()
    owned = sp.owned_cols()
    n_max = plan.n_max
    G = plan.num_segments
    fam = get_family(plan.family)
    if theta0 is None:
        theta0 = jnp.zeros((G,), jnp.float32)

    def _local_wcol(rank):
        """This rank's slice of the packed per-column weight vector: a
        column-sharded entry owns the contiguous GSPMD block
        [rank*m_loc, (rank+1)*m_loc) of its global weights; replicated
        entries carry them whole. Lane padding weights 1.0."""
        parts = []
        for e, sh in zip(sp.local.entries, sp.col_sharded):
            if e.weights is None:
                parts.append(jnp.ones((e.lead * e.m_pad,), jnp.float32))
                continue
            wg = jnp.asarray(e.weights, jnp.float32)
            w_loc = (jax.lax.dynamic_slice(wg, (rank * e.m,), (e.m,))
                     if sh else wg)
            w_loc = jnp.pad(w_loc, (0, e.m_pad - e.m), constant_values=1.0)
            parts.append(jnp.tile(w_loc, e.lead))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def body(th0, *lv):
        rank = jnp.zeros((), jnp.int32)
        for a in axis_names:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        contrib = jnp.logical_or(jnp.asarray(owned), rank == 0)
        pieces = [_pack_entry(x, e, n_max)
                  for x, e in zip(lv, sp.local.entries)]
        Ypk = jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]
        w_col = _local_wcol(rank) if fam.uses_weights else None
        Xpk, theta, iters = project_segmented_family_sharded(
            Ypk, jnp.asarray(sids), jnp.asarray(C_seg), num_segments=G,
            axis_names=axis_names, family=plan.family, w_col=w_col,
            theta0=th0, contrib=contrib, max_iter=max_iter)
        outs = []
        for x, e in zip(lv, sp.local.entries):
            block = jax.lax.slice_in_dim(
                Xpk, e.col_start, e.col_start + e.lead * e.m_pad, axis=1)
            outs.append(_unpack_entry(block, e, x))
        return tuple(outs), theta, iters

    leaf_specs = tuple(_leaf_spec(e, sh, axis_names)
                       for e, sh in zip(plan.entries, sp.col_sharded))
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None),) + leaf_specs,
                   out_specs=(leaf_specs, P(None), P()),
                   check_vma=False)
    outs, theta, iters = fn(jnp.asarray(theta0, jnp.float32), *leaves)
    return list(outs), theta, iters


def _local_virtual_wcol(sp: ShardedPlan, rank):
    """This rank's slice of the DENSE per-column weight vector (the
    virtual-packing twin of ``_local_wcol``): a column-sharded entry owns
    the contiguous GSPMD block [rank*m_loc, (rank+1)*m_loc) of its global
    weights; replicated entries carry them whole. No lane padding exists
    in the dense layout, so no 1.0 filler is inserted."""
    parts = []
    for e, sh in zip(sp.local.entries, sp.col_sharded):
        if e.weights is None:
            parts.append(jnp.ones((e.lead * e.m,), jnp.float32))
            continue
        wg = jnp.asarray(e.weights, jnp.float32)
        w_loc = (jax.lax.dynamic_slice(wg, (rank * e.m,), (e.m,))
                 if sh else wg)
        parts.append(jnp.tile(w_loc, e.lead))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def fused_plan_sharded(plan: PackedPlan, mesh: Mesh,
                       g_leaves: Sequence[jnp.ndarray],
                       m_leaves: Sequence[jnp.ndarray],
                       v_leaves: Sequence[jnp.ndarray],
                       p_leaves: Sequence[jnp.ndarray],
                       mask_leaves: Sequence[Optional[jnp.ndarray]],
                       *, acfg, lr_t, b1c, b2c, scale=None,
                       theta0: Optional[jnp.ndarray] = None,
                       max_iter: int = 32):
    """The PR-7 two-HBM-pass megakernel inside shard_map, shards resident.

    One fused optimizer+projection step for one packed plan whose family
    streams its Newton aux from per-column statistics (``from_colstats``):

      * pass 1 (``fused_adam_colstats``) runs RANK-LOCAL on each rank's
        column shard — rows are resident, so every per-column (sum, max)
        statistic is bitwise the gathered value;
      * the per-segment reductions cross the mesh inside the warm-started
        segmented Newton with ONE stacked (2, num_segments) f32 psum per
        Eq.-(19) evaluation (never an all-gather; ``shard_packed_plan``'s
        owned-columns/contrib machinery counts replicated leaves once);
      * pass 2 (``fused_adam_clip_apply``) recomputes u from the moments
        pass 1 just wrote and clips rank-local — PR 7's moment-consistent
        recompute invariant holds bit-for-bit per rank.

    ``g/m/v/p/mask_leaves`` are the plan entries' leaf arrays in entry
    order (any sharding — GSPMD reshards to the canonical column layout
    at the shard_map boundary, an all-to-all of |leaf|/D bytes per rank);
    ``mask_leaves`` entries may be None. ``lr_t``/``b1c``/``b2c``/``scale``
    are the traced step scalars (``optim.adam.adam_scalars`` /
    ``clip_scale``). Returns ``(p_new, m_new, v_new, theta, iters)`` with
    the leaf lists in entry order (input shardings preserved), theta
    (num_segments,) f32 replicated. Params match the gathered fused solve
    up to the fp reduction order of the theta psums.

    >>> ps, ms, vs, th, it = fused_plan_sharded(plan, mesh, gs, ms0, vs0,
    ...     ps0, [None]*len(gs), acfg=acfg, lr_t=lr_t, b1c=b1c, b2c=b2c)
    """
    from ..core.engine import _MU_INF
    from ..core.l1inf import _segmented_newton
    from ..kernels.fused_step import (fused_adam_clip_apply,
                                      fused_adam_colstats)

    axis_names = tuple(mesh.axis_names)
    D = int(np.prod([mesh.shape[a] for a in axis_names], dtype=np.int64))
    sp = shard_packed_plan(plan, D)
    sids = sp.local.virtual_seg_ids()
    C_seg = plan.radii()
    owned = sp.virtual_owned_cols()
    G = plan.num_segments
    fam = get_family(plan.family)
    stat = getattr(fam.seg_ops, "colstats_stat", "abs")
    mode = getattr(fam.seg_ops, "fused_mode", "clip")
    if theta0 is None:
        theta0 = jnp.zeros((G,), jnp.float32)
    sc = {"lr_t": jnp.asarray(lr_t, jnp.float32),
          "b1c": jnp.asarray(b1c, jnp.float32),
          "b2c": jnp.asarray(b2c, jnp.float32)}
    if scale is not None:
        sc["scale"] = jnp.asarray(scale, jnp.float32)

    def body(th0, sc, gs, ms, vs, ps, mks):
        rank = jnp.zeros((), jnp.int32)
        for a in axis_names:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        contrib = jnp.logical_or(jnp.asarray(owned), rank == 0)
        sids_a = jnp.asarray(sids)
        # pass 1, rank-local: moments written, O(m_loc) statistics out —
        # the updated values never reach HBM, the shard never moves
        new_m, new_v, sums, maxes = [], [], [], []
        for g, m, v, p, mk, e in zip(gs, ms, vs, ps, mks, sp.local.entries):
            mn, vn, cs, cm = fused_adam_colstats(
                g, m, v, p, cfg=acfg, lr_t=sc["lr_t"], b1c=sc["b1c"],
                b2c=sc["b2c"], scale=sc.get("scale"), mask=mk,
                transpose=e.transpose, stat=stat)
            new_m.append(mn)
            new_v.append(vn)
            sums.append(cs.reshape(-1))
            maxes.append(cm.reshape(-1))
        colsum = jnp.concatenate(sums) if len(sums) > 1 else sums[0]
        colmax = jnp.concatenate(maxes) if len(maxes) > 1 else maxes[0]
        w_col = _local_virtual_wcol(sp, rank) if fam.uses_weights else None
        aux = fam.seg_ops.from_colstats(colsum, colmax, w_col)
        mu, theta, iters, inside_seg, zero_seg = _segmented_newton(
            aux, sids_a, jnp.asarray(C_seg), G, th0, max_iter,
            ops=fam.seg_ops, axis_names=axis_names, contrib=contrib)
        # fold the identity/zero segment gating into the clip level, as in
        # the single-device fused step — no padding exists in the dense
        # layout, so the lookups need no sentinel extension
        if mode == "scale":
            lvl = fam.seg_ops.fused_scale(aux, mu)
            mu_eff = jnp.where(zero_seg[sids_a], 0.0,
                               jnp.where(inside_seg[sids_a], 1.0, lvl))
        else:
            mu_eff = jnp.where(zero_seg[sids_a], 0.0,
                               jnp.where(inside_seg[sids_a], _MU_INF, mu))
        # pass 2, rank-local: recompute u from the just-written moments,
        # clip at mu — the step's only param write, shard still resident
        new_p, off = [], 0
        for p, mn, vn, mk, e in zip(ps, new_m, new_v, mks,
                                    sp.local.entries):
            span = e.lead * e.m
            mu_leaf = mu_eff[off:off + span].reshape(e.lead, e.m)
            off += span
            new_p.append(fused_adam_clip_apply(
                mn, vn, p, mu_leaf, cfg=acfg, lr_t=sc["lr_t"],
                b1c=sc["b1c"], b2c=sc["b2c"], mask=mk,
                transpose=e.transpose, mode=mode))
        return tuple(new_p), tuple(new_m), tuple(new_v), theta, iters

    leaf_specs = tuple(_leaf_spec(e, sh, axis_names)
                       for e, sh in zip(plan.entries, sp.col_sharded))
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None), P(), leaf_specs, leaf_specs,
                             leaf_specs, leaf_specs, leaf_specs),
                   out_specs=(leaf_specs, leaf_specs, leaf_specs,
                              P(None), P()),
                   check_vma=False)
    new_p, new_m, new_v, theta, iters = fn(
        jnp.asarray(theta0, jnp.float32), sc, tuple(g_leaves),
        tuple(m_leaves), tuple(v_leaves), tuple(p_leaves),
        tuple(mask_leaves))
    return list(new_p), list(new_m), list(new_v), theta, iters
