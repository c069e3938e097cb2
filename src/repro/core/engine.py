"""ProjectionEngine: ONE projected-update path for every train loop.

PR 2 left three hand-rolled copies of "adam_update -> packed projection ->
every_k gate" (train/loop.py, sae/train.py, launch/steps.py), each wiring the
packing, theta warm-start state, and gating by hand — and the production
launch path cold-started Newton every step because nothing threaded the
state. This module centralizes the runtime side of the constraint system:

  * ``ProjectionEngine`` owns plan building (``core.constraints``), packing,
    per-plan theta state, and solver dispatch:
      - ``newton``  — single-buffer segmented Newton (default, 1 device);
      - ``pallas``  — fused-kernel engine (compiled on TPU, interpreted
        elsewhere: ``kernels.backend.resolve_impl``);
      - ``sharded`` — mesh-resident shard_map solve (``dist.projection``):
        weight shards never gather; per-segment statistics cross the link
        as one (num_segments,) psum per Newton evaluation.
  * ``engine.apply(params, step=, state=)`` projects a param pytree —
    the packed fast path plus the per-leaf fallback for unpackable norms.
  * ``engine.projected_update(grads, opt_state, params, acfg, ...)`` is the
    shared step core all three train loops build on: optimizer update,
    projection, optional support-mask freeze, warm-start state threading.

The theta warm-start contract (DESIGN.md §1/§7): each plan's state entry is
the previous solve's per-segment theta vector; passing it back makes
steady-state solves converge in the 2 bootstrap Eq.-(19) evaluations.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .constraints import (ProjectionSpec, build_packed_plans, engine_count,
                          _apply_2d, _gated, _pack_entry, _project_fn,
                          _unpack_entry)
from .families import get_family, project_segmented_family
from .l1inf import _segmented_newton

__all__ = ["ProjectionEngine", "apply_constraints_packed",
           "init_projection_state"]

_SOLVERS = ("newton", "pallas", "sharded", "fused", "fused_sharded")

# Identity sentinel for the fused clip pass: a per-column clip level far
# above any parameter magnitude, so sign(u) * min(|u|, _MU_INF) == u exactly
# (segments already inside the ball must pass through untouched).
_MU_INF = 1e30


class ProjectionEngine:
    """Plan building + theta state + solver dispatch for projection specs.

    Construct once per step-build (the specs and solver are static); call
    ``apply``/``projected_update`` inside the traced step. ``solver`` is the
    default for every packed plan ("newton" | "pallas" | "sharded" |
    "fused" | "fused_sharded"); ``mesh`` is required for "sharded" and
    "fused_sharded". "fused" runs the two-HBM-pass optimizer+projection
    megakernel inside ``projected_update`` for every plan whose family
    provides the ``from_colstats`` streaming hook at ``every_k == 1``
    (DESIGN.md §11) and is bit-identical to "newton" everywhere else
    (``apply`` and all fallback plans solve exactly as "newton" would).
    "fused_sharded" is the mesh twin (DESIGN.md §12): the same two passes
    run rank-local inside shard_map on each rank's column shard
    (``dist.projection.fused_plan_sharded``) with one stacked
    (2, num_segments) psum per Newton evaluation, and every plan the
    megakernel cannot take falls back to the "sharded" shard_map Newton —
    bit-identical to what ``solver="sharded"`` would produce. The engine
    itself is stateless — the theta warm-start dict returned by
    ``init_state`` threads through the caller's train state.

    >>> engine = ProjectionEngine((spec,)); state = engine.init_state(params)
    """

    def __init__(self, specs: Sequence[ProjectionSpec],
                 *, solver: str = "newton", mesh=None):
        if solver not in _SOLVERS:
            raise ValueError(f"unknown solver {solver!r} (one of {_SOLVERS})")
        if solver in ("sharded", "fused_sharded") and mesh is None:
            raise ValueError(f"solver={solver!r} needs a mesh")
        self.specs = tuple(specs or ())
        self.solver = solver
        self.mesh = mesh

    # -- static plan/state helpers (shape-only, safe while tracing) ---------

    def plans(self, params: Any):
        """(packed plans, per-leaf remainder) for this param pytree."""
        return build_packed_plans(params, self.specs)

    def init_state(self, params: Any) -> Dict[str, Any]:
        """Zero theta warm-start vectors, one per packed plan (pytree-safe,
        works on ShapeDtypeStructs for dry-run lowering)."""
        plans, _ = self.plans(params)
        return {p.key: jnp.zeros((p.num_segments,), jnp.float32)
                for p in plans}

    # -- the projection ------------------------------------------------------

    def _solve_plan(self, plan, leaves, theta0):
        """One packed solve of one family sub-buffer. Returns
        (projected-by-leaf-index dict, theta, iters). The constraint family
        named by the plan supplies the per-column Newton statistics
        (``core.families``); a family without a fused-kernel implementation
        falls back to the packed Newton path under solver='pallas', and
        plans the fused step cannot take (``projected_update`` dispatches
        those here) solve exactly as solver='newton' — or, under
        solver='fused_sharded', exactly as solver='sharded' (the shard_map
        Newton, shards resident)."""
        eff = {"fused": "newton",
               "fused_sharded": "sharded"}.get(self.solver, self.solver)
        engine_count(f"{plan.key}/{eff}")
        fam = get_family(plan.family)
        if eff == "sharded":
            from ..dist.projection import project_plan_sharded
            vals = [leaves[e.index] for e in plan.entries]
            outs, theta, iters = project_plan_sharded(
                vals, plan, self.mesh, theta0=theta0)
            return dict(zip((e.index for e in plan.entries), outs)), \
                theta, iters
        pieces = [_pack_entry(leaves[e.index], e, plan.n_max)
                  for e in plan.entries]
        Ypk = jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]
        sids = jnp.asarray(plan.seg_ids())
        C_seg = jnp.asarray(plan.radii())
        w_col = jnp.asarray(plan.col_weights()) if fam.uses_weights else None
        if self.solver == "pallas" and fam.pallas_loader is not None:
            pallas_fn = fam.pallas_loader()
            Xpk, theta = pallas_fn(
                Ypk, sids, C_seg, num_segments=plan.num_segments,
                theta0=theta0)
            iters = jnp.asarray(-1, jnp.int32)   # kernel keeps its own count
        else:
            Xpk, theta, iters = project_segmented_family(
                Ypk, sids, C_seg, num_segments=plan.num_segments,
                family=plan.family, w_col=w_col, theta0=theta0)
        outs = {}
        for e in plan.entries:
            block = jax.lax.slice_in_dim(
                Xpk, e.col_start, e.col_start + e.lead * e.m_pad, axis=1)
            outs[e.index] = _unpack_entry(block, e, leaves[e.index])
        return outs, theta, iters

    def apply(self, params: Any, *, step: Optional[jnp.ndarray] = None,
              state: Optional[Dict[str, Any]] = None,
              with_stats: bool = False):
        """Project matching leaves of ``params``.

        Leaves are packed into ONE buffer per (constraint family, every_k)
        pair and each sub-buffer is projected by a single solve of the
        configured solver — a mixed-family spec list (plain + weighted +
        bilevel, same every_k) costs one engine invocation per family;
        unpackable norms (the l1 ball and per-leaf-only families like
        hoyer) fall back to the per-leaf path. ``state`` threads the
        per-plan theta vectors (Newton warm start) between train steps —
        pass the dict from ``init_state`` (or a previous call) and reuse
        the returned dict. ``step`` gates ``every_k > 1`` specs.

        Returns (params, new_state), plus a {plan.key: Eq.-(19) eval count}
        stats dict when ``with_stats``. Results are bit-equal (up to fp
        accumulation order) to per-matrix projection on every leaf,
        whichever solver runs.
        """
        if not self.specs:
            out = (params, dict(state or {}))
            return out + ({},) if with_stats else out
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [leaf for _, leaf in flat]
        plans, per_leaf = self.plans(params)
        new_state: Dict[str, Any] = {}
        stats: Dict[str, Any] = {}

        for plan in plans:
            theta0 = None if state is None else state.get(plan.key)
            projected, theta, iters = self._solve_plan(plan, leaves, theta0)
            for e in plan.entries:
                leaves[e.index] = _gated(projected[e.index], leaves[e.index],
                                         step, plan.every_k)
            if step is not None and plan.every_k > 1:
                do = (step % plan.every_k) == 0
                prev = theta0 if theta0 is not None else jnp.zeros_like(theta)
                theta = jnp.where(do, theta, prev)
            new_state[plan.key] = theta
            stats[plan.key] = iters

        for i, spec in per_leaf:
            engine_count("per_leaf")
            fn = _project_fn(spec)
            projected = _apply_2d(fn, leaves[i], spec.radius, spec.axis)
            leaves[i] = _gated(projected, leaves[i], step, spec.every_k)

        params = jax.tree_util.tree_unflatten(treedef, leaves)
        if with_stats:
            return params, new_state, stats
        return params, new_state

    # -- the shared projected-update step core -------------------------------

    def projected_update(self, grads: Any, opt_state, params: Any, acfg,
                         *, lr=None, mask: Any = None,
                         state: Optional[Dict[str, Any]] = None,
                         with_stats: bool = False,
                         grad_reduce: Optional[Any] = None):
        """Optimizer update + projection + gating: the step core shared by
        train/loop.py, sae/train.py, and launch/steps.py.

        Runs ``adam_update`` (with optional ``lr`` schedule override and
        ``mask`` gradient freeze), projects through ``apply`` gated on the
        NEW optimizer count, re-applies ``mask`` to the params afterwards
        (the double-descent support freeze — projection may revive a clipped
        column, the mask keeps it dead), and threads the theta state.

        Under ``solver="fused"``, plans whose family streams its Newton
        statistics (``from_colstats``) at ``every_k == 1`` take the
        two-HBM-pass fused step instead (``kernels/fused_step``,
        DESIGN.md §11): pass 1 is the Adam update and the per-column
        statistics in one read of (grad, mu, nu, param), the segmented
        Newton runs on O(num_segments) state, pass 2 recomputes the update
        from the just-written moments and writes the clipped params — the
        unclipped parameters never reach HBM and no packed buffer exists.
        Everything else (per-leaf specs, ``every_k``-gated plans, families
        without the hook) falls back to this unfused path, leaf-exact.
        ``solver="fused_sharded"`` runs the same two passes rank-local
        inside shard_map (``dist.projection.fused_plan_sharded``); its
        fallback plans take the shard_map Newton instead, so no path
        gathers a weight shard.

        ``grad_reduce``: optional callable applied to ``grads`` FIRST —
        the hook for explicit-collective data-parallel callers whose grads
        are still per-rank partials (e.g. ``dist.compression
        .compressed_psum`` inside a shard_map'd DP step; see
        examples/compressed_dp.py). The reduction composes with the
        projection in one jitted step and leaves the projection's
        one-psum-per-eval contract untouched. Under GSPMD ``jax.grad``
        grads arrive already reduced — leave it None there.

        Returns (params, opt_state, proj_state) (+ stats when requested).
        """
        if grad_reduce is not None:
            grads = grad_reduce(grads)
        if self.solver in ("fused", "fused_sharded") and self.specs:
            plans, per_leaf = self.plans(params)
            fused_plans = [
                p for p in plans
                if p.every_k == 1
                and hasattr(get_family(p.family).seg_ops, "from_colstats")]
            if fused_plans:
                return self._projected_update_fused(
                    grads, opt_state, params, acfg, lr=lr, mask=mask,
                    state=state, plans=plans, per_leaf=per_leaf,
                    fused_plans=fused_plans, with_stats=with_stats)
        from ..optim.adam import adam_update
        new_params, new_opt = adam_update(grads, opt_state, params, acfg,
                                          lr=lr, mask=mask)
        stats: Dict[str, Any] = {}
        if self.specs:
            new_params, state, stats = self.apply(
                new_params, step=new_opt.count, state=state, with_stats=True)
            if mask is not None:
                new_params = jax.tree_util.tree_map(
                    lambda p, m: p * m, new_params, mask)
        else:
            state = dict(state or {})
        if with_stats:
            return new_params, new_opt, state, stats
        return new_params, new_opt, state

    def _projected_update_fused(self, grads, opt_state, params: Any, acfg,
                                *, lr, mask, state, plans, per_leaf,
                                fused_plans, with_stats):
        """The two-HBM-pass step (DESIGN.md §11). ``fused_plans`` take the
        megakernel; every other plan/leaf replays the unfused path on the
        already-updated leaves, so mixed spec lists stay exact."""
        from ..optim.adam import (AdamState, adam_leaf_update, adam_scalars,
                                  clip_scale)
        from ..kernels.fused_step import (fused_adam_clip_apply,
                                          fused_adam_colstats)

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        m_leaves = jax.tree_util.tree_leaves(opt_state.mu)
        v_leaves = jax.tree_util.tree_leaves(opt_state.nu)
        mk_leaves = (jax.tree_util.tree_leaves(mask) if mask is not None
                     else [None] * len(p_leaves))

        count = opt_state.count + 1
        lr_t, b1c, b2c = adam_scalars(acfg, count, lr)
        scale = (clip_scale(grads, acfg.clip_norm)
                 if acfg.clip_norm is not None else None)

        fused_idx = {e.index for plan in fused_plans for e in plan.entries}
        new_p, new_m, new_v = (list(p_leaves), list(m_leaves), list(v_leaves))
        for i in range(len(p_leaves)):
            if i in fused_idx:
                continue
            new_p[i], new_m[i], new_v[i] = adam_leaf_update(
                g_leaves[i], m_leaves[i], v_leaves[i], p_leaves[i], acfg,
                lr_t, b1c, b2c, mask=mk_leaves[i], scale=scale)

        new_state: Dict[str, Any] = {}
        stats: Dict[str, Any] = {}
        for plan in fused_plans:
            engine_count(f"{plan.key}/{self.solver}")
            fam = get_family(plan.family)
            theta0 = None if state is None else state.get(plan.key)
            if self.solver == "fused_sharded":
                # mesh path: both passes + the one-psum-per-eval Newton run
                # inside shard_map with the column shards resident
                from ..dist.projection import fused_plan_sharded
                idx = [e.index for e in plan.entries]
                ps, ms, vs, theta, iters = fused_plan_sharded(
                    plan, self.mesh,
                    [g_leaves[i] for i in idx], [m_leaves[i] for i in idx],
                    [v_leaves[i] for i in idx], [p_leaves[i] for i in idx],
                    [mk_leaves[i] for i in idx],
                    acfg=acfg, lr_t=lr_t, b1c=b1c, b2c=b2c, scale=scale,
                    theta0=theta0)
                for i, p_i, m_i, v_i in zip(idx, ps, ms, vs):
                    new_p[i], new_m[i], new_v[i] = p_i, m_i, v_i
                new_state[plan.key] = theta
                stats[plan.key] = iters
                continue
            stat = getattr(fam.seg_ops, "colstats_stat", "abs")
            mode = getattr(fam.seg_ops, "fused_mode", "clip")
            sums, maxes = [], []
            # pass 1: one read of (grad, mu, nu, param) per leaf -> moments
            # written, O(m) statistics out, the updated values never stored
            for e in plan.entries:
                i = e.index
                new_m[i], new_v[i], cs, cm = fused_adam_colstats(
                    g_leaves[i], m_leaves[i], v_leaves[i], p_leaves[i],
                    cfg=acfg, lr_t=lr_t, b1c=b1c, b2c=b2c,
                    scale=scale, mask=mk_leaves[i], transpose=e.transpose,
                    stat=stat)
                sums.append(cs.reshape(-1))
                maxes.append(cm.reshape(-1))
            colsum = jnp.concatenate(sums) if len(sums) > 1 else sums[0]
            colmax = jnp.concatenate(maxes) if len(maxes) > 1 else maxes[0]
            sids = jnp.asarray(plan.virtual_seg_ids())
            C_seg = jnp.asarray(plan.radii())
            w_col = (jnp.asarray(plan.virtual_col_weights())
                     if fam.uses_weights else None)
            aux = fam.seg_ops.from_colstats(colsum, colmax, w_col)
            mu, theta, iters, inside_seg, zero_seg = _segmented_newton(
                aux, sids, C_seg, plan.num_segments, theta0, 32,
                ops=fam.seg_ops)
            # fold the identity/zero segment gating into the per-column
            # level so pass 2 is a single min()/multiply — no virtual
            # columns are padding, so the lookups need no sentinel
            # extension. Clip families gate with the 1e30 clip sentinel;
            # scale families (l1,2) turn mu into the column multiplier via
            # fused_scale and gate with the 1.0 identity multiplier.
            if mode == "scale":
                lvl = fam.seg_ops.fused_scale(aux, mu)
                mu_eff = jnp.where(zero_seg[sids], 0.0,
                                   jnp.where(inside_seg[sids], 1.0, lvl))
            else:
                mu_eff = jnp.where(zero_seg[sids], 0.0,
                                   jnp.where(inside_seg[sids], _MU_INF, mu))
            off = 0
            # pass 2: recompute the update from the just-written moments,
            # clip/scale at mu, write the params — the step's only param
            # write
            for e in plan.entries:
                span = e.lead * e.m
                mu_leaf = mu_eff[off:off + span].reshape(e.lead, e.m)
                off += span
                i = e.index
                new_p[i] = fused_adam_clip_apply(
                    new_m[i], new_v[i], p_leaves[i], mu_leaf,
                    cfg=acfg, lr_t=lr_t, b1c=b1c, b2c=b2c,
                    mask=mk_leaves[i], transpose=e.transpose, mode=mode)
            new_state[plan.key] = theta
            stats[plan.key] = iters

        # unfused remainder: every_k-gated plans and families without the
        # streaming hook (packed Newton), then unpackable per-leaf norms
        fused_keys = {plan.key for plan in fused_plans}
        for plan in plans:
            if plan.key in fused_keys:
                continue
            theta0 = None if state is None else state.get(plan.key)
            projected, theta, iters = self._solve_plan(plan, new_p, theta0)
            for e in plan.entries:
                new_p[e.index] = _gated(projected[e.index], new_p[e.index],
                                        count, plan.every_k)
            if plan.every_k > 1:
                do = (count % plan.every_k) == 0
                prev = (theta0 if theta0 is not None
                        else jnp.zeros_like(theta))
                theta = jnp.where(do, theta, prev)
            new_state[plan.key] = theta
            stats[plan.key] = iters

        for i, spec in per_leaf:
            engine_count("per_leaf")
            fn = _project_fn(spec)
            projected = _apply_2d(fn, new_p[i], spec.radius, spec.axis)
            new_p[i] = _gated(projected, new_p[i], count, spec.every_k)

        if mask is not None:
            # support freeze on the unfused leaves; the fused clip pass
            # already multiplies its output by the mask in-kernel
            for i in range(len(new_p)):
                if i not in fused_idx:
                    new_p[i] = new_p[i] * mk_leaves[i]

        new_params = jax.tree_util.tree_unflatten(treedef, new_p)
        new_opt = AdamState(count=count,
                            mu=jax.tree_util.tree_unflatten(treedef, new_m),
                            nu=jax.tree_util.tree_unflatten(treedef, new_v))
        if with_stats:
            return new_params, new_opt, new_state, stats
        return new_params, new_opt, new_state


# ---------------------------------------------------------------------------
# functional wrappers (the PR-2 API, now thin shims over the engine)
# ---------------------------------------------------------------------------

def init_projection_state(params: Any,
                          specs: Sequence[ProjectionSpec]) -> Dict[str, Any]:
    """Zero theta warm-start vectors, one per packed plan (pytree-safe).

    ``params``: pytree of arrays or ShapeDtypeStructs (only shapes are
    read). Returns ``{plan key: (num_segments,) f32 zeros}`` — the state
    threaded through ``apply_constraints_packed`` between steps.

    >>> state = init_projection_state(params, specs)
    """
    return ProjectionEngine(specs).init_state(params)


def apply_constraints_packed(params: Any, specs: Sequence[ProjectionSpec],
                             step: Optional[jnp.ndarray] = None,
                             state: Optional[Dict[str, Any]] = None,
                             engine: str = "newton", mesh=None):
    """Project matching leaves with packed multi-tensor batching.

    Functional form of ``ProjectionEngine.apply`` — ``engine`` picks the
    solver ("newton" | "pallas" | "sharded"; the latter needs ``mesh``).
    ``params``: any pytree; ``step``: optional scalar int (every_k gating);
    ``state``: the dict from ``init_projection_state`` or a previous call.
    Returns (projected params, new_state).

    >>> params, state = apply_constraints_packed(params, specs, state=state)
    """
    return ProjectionEngine(specs, solver=engine, mesh=mesh).apply(
        params, step=step, state=state)
