"""Training-time integration: structured-sparsity constraints on param pytrees.

A ``ProjectionSpec`` selects parameter leaves by path regex and applies one of
the ball projections after each optimizer update (projected gradient descent,
the paper's Algorithm 3). Leaves with more than 2 dims (scan-stacked layers,
stacked experts) are vmapped over their leading dims so the constraint applies
per layer / per expert.

Packed multi-tensor batching: instead of one projection launch per matching
weight matrix, every leaf of a registered constraint family
(``core.families``) is canonicalized (max axis -> 0), lane-padded, and
concatenated into ONE (n_max, sum m) buffer per (family, every_k) pair with
a per-column segment id; a stacked (L, n, m) leaf contributes L segments,
so the packing subsumes the per-layer vmap. Each family sub-buffer is
projected by ``families.project_segmented_family`` in a single fused sweep
— one compile, one launch, one HBM pass per family per train step — and
unpacked exactly (slicing off padding). Per-segment radii ride in a C
vector and weight-aware families a per-column w vector, so specs with
different radii/weights still share one launch. A per-plan theta vector
threads through the train state as next step's Newton warm start (plan
keys isolate warm starts per family — thetas never cross families).

This module owns the STATIC side of that story — specs, leaf matching, plan
building, pack/unpack, masks/reports (the invocation counters live in
``repro.obs`` and are re-exported here). The runtime side (solver
dispatch newton|pallas|sharded, theta state, the shared projected-update
step core) lives in ``core.engine``; the mesh-resident distributed solve
lives in ``dist.projection``.

This module is what makes the paper's technique a first-class framework
feature: every arch config carries a tuple of specs (see configs/*.py).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..obs import engine_count, engine_counters, engine_counters_reset
from .families import family_for_norm, get_family, registered_norms
from .norms import project_l1_ball

__all__ = ["ProjectionSpec", "apply_constraints", "build_packed_plans",
           "column_masks", "apply_masks", "sparsity_report", "leaf_path_str",
           "engine_count", "engine_counters", "engine_counters_reset"]

# spec norms: every registered constraint family's norms (packable families
# pack into per-family sub-buffers; seg_ops=None families like hoyer route
# per-leaf) plus the hand-wired per-leaf-only l1 ball
_EXTRA_NORMS = {"l1"}


def _known_norms():
    return registered_norms() | _EXTRA_NORMS
_LANE = 128   # TPU lane width: per-matrix column padding unit
_SUBLANE = 8  # TPU sublane: packed-buffer row padding unit


@dataclasses.dataclass(frozen=True)
class ProjectionSpec:
    """One structured-sparsity constraint.

    pattern:  regex matched against the '/'-joined param path.
    norm:     a registered constraint-family norm (l1inf | l1inf_sorted |
              l1inf_weighted | l1inf_masked | bilevel | l12 | hoyer — see
              ``core.families``; hoyer's radius is the target sparseness
              ratio s in (0, 1]) or the per-leaf-only l1 ball.
    radius:   ball radius C (> 0).
    axis:     the *max* axis of the trailing 2-D slice (paper: 0 — columns
              are prunable structures along the other axis).
    every_k:  apply every k optimizer steps (1 = every step).
    weights:  per-column weights for the l1inf_weighted family (a tuple of
              floats, one per canonical column of every matching leaf;
              None = uniform 1.0). Stored as a static tuple so specs stay
              hashable/trace-safe.

    Hashable/frozen — carry tuples of specs in static config (configs/*.py).

    >>> spec = ProjectionSpec(pattern=r"enc1/w", norm="l1inf", radius=0.1, axis=1)
    """
    pattern: str
    norm: str = "l1inf"
    radius: float = 1.0
    axis: int = 0
    every_k: int = 1
    weights: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        if self.norm not in _known_norms():
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.radius <= 0:
            raise ValueError("radius must be > 0")
        if self.weights is not None:
            fam = family_for_norm(self.norm)
            if fam is None or not fam.uses_weights:
                raise ValueError(
                    f"norm {self.norm!r} does not take per-column weights")
            w = tuple(float(x) for x in self.weights)
            if any(x <= 0 for x in w):
                raise ValueError("weights must be > 0")
            object.__setattr__(self, "weights", w)


def leaf_path_str(path) -> str:
    """'/'-joined name of one pytree leaf path — the string spec patterns
    match against.

    ``path``: the key-path tuple from ``jax.tree_util``'s ``_with_path``
    APIs (dict keys, sequence indices, and attribute names all stringify).
    Returns e.g. ``"enc1/w"`` for ``params["enc1"]["w"]``.

    >>> name = leaf_path_str(path)   # from tree_flatten_with_path
    """
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _project_fn(spec: "ProjectionSpec") -> Callable:
    """Per-leaf projection (x_2d, C, axis) -> x_2d for one spec.

    Family norms — l12 and hoyer included — dispatch through the registry
    (``l1inf_sorted`` keeps the total-order solver on this path); only the
    flat l1 ball stays hand-wired.
    """
    if spec.norm == "l1inf_sorted":
        from .l1inf import project_l1inf_sorted
        return lambda x, C, axis: project_l1inf_sorted(x, C, axis=axis)
    if spec.norm == "l1":
        return lambda x, C, axis: project_l1_ball(x, C)
    fam = family_for_norm(spec.norm)
    w = spec.weights

    def fn(x, C, axis):
        wj = None if w is None else jnp.asarray(w, jnp.float32)
        return fam.project_leaf(x, C, axis=axis, w=wj)

    return fn


def _apply_2d(fn: Callable, x: jnp.ndarray, C: float, axis: int) -> jnp.ndarray:
    """Apply a 2-D projection to the trailing 2 dims, vmapping leading dims."""
    if x.ndim < 2:
        raise ValueError(f"projection target must have >=2 dims, got {x.shape}")
    if x.ndim == 2:
        return fn(x, C, axis)
    lead = x.shape[: x.ndim - 2]
    flat = x.reshape((-1,) + x.shape[-2:])
    out = jax.vmap(lambda m: fn(m, C, axis))(flat)
    return out.reshape(lead + x.shape[-2:])


def _first_match(specs: Sequence[ProjectionSpec], name: str, leaf):
    for spec in specs:
        if re.search(spec.pattern, name) and hasattr(leaf, "ndim") \
                and leaf.ndim >= 2:
            if spec.weights is not None:
                # canonical columns = the non-max axis of the trailing slice
                m = leaf.shape[-2 if spec.axis in (1, -1) else -1]
                if len(spec.weights) != m:
                    raise ValueError(
                        f"spec {spec.pattern!r}: {len(spec.weights)} weights "
                        f"for a leaf with {m} canonical columns "
                        f"(shape {tuple(leaf.shape)})")
            return spec
    return None


def _gated(projected, original, step, every_k):
    if step is not None and every_k > 1:
        do = (step % every_k) == 0
        return jax.tree_util.tree_map(
            lambda p, o: jnp.where(do, p, o), projected, original)
    return projected


def apply_constraints(params: Any, specs: Sequence[ProjectionSpec],
                      step: Optional[jnp.ndarray] = None) -> Any:
    """Project matching leaves of `params`, one launch per matrix.

    ``params``: any pytree (constrained leaves must be >= 2-D, any float
    dtype — the solve runs in f32 and casts back); ``specs``: ordered —
    first matching spec wins per leaf; ``step``: optional scalar int for
    ``every_k`` gating. Returns the projected pytree, same structure/
    dtypes. jit-safe (cond on step % every_k). The packed fast path is
    ``apply_constraints_packed``; this per-leaf form stays as the simple
    reference used by tests and the per-leaf-only norms (l1, hoyer).

    >>> params = apply_constraints(params, (spec,))
    """
    if not specs:
        return params
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    leaves = []
    for path, leaf in flat:
        spec = _first_match(specs, leaf_path_str(path), leaf)
        out = leaf
        if spec is not None:
            engine_count("per_leaf")
            fn = _project_fn(spec)
            projected = _apply_2d(fn, out, spec.radius, spec.axis)
            out = _gated(projected, out, step, spec.every_k)
        leaves.append(out)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -----------------------------------------------------------------------------
# packed multi-tensor batching
# -----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _PackedEntry:
    """One leaf's slot inside a packed plan (all fields static)."""
    index: int                 # position in the flattened leaf list
    shape: Tuple[int, ...]     # original leaf shape
    lead: int                  # number of stacked (leading-dim) matrices
    n: int                     # canonical max-axis length
    m: int                     # canonical column count per matrix
    transpose: bool            # spec.axis selected the trailing dim
    radius: float
    m_pad: int                 # m padded up to the lane multiple
    col_start: int             # first column in the packed buffer
    seg_start: int             # first segment id
    weights: Optional[Tuple[float, ...]] = None   # per canonical column


@dataclasses.dataclass(frozen=True)
class PackedPlan:
    """Static packing layout for one (family, every_k) sub-buffer.

    Mixed-family spec lists split into one plan — one packed solve — per
    constraint family (``core.families``): families differ in their
    per-column Newton statistics and their thetas live on different scales,
    so segments never mix across families, but everything of ONE family
    with one ``every_k`` still solves in a single fused sweep.
    """
    key: str
    every_k: int
    n_max: int                 # padded row count of the packed buffer
    total_cols: int
    num_segments: int
    entries: Tuple[_PackedEntry, ...]
    family: str = "l1inf"

    def seg_ids(self) -> np.ndarray:
        """Per-column segment id; ``num_segments`` marks lane padding."""
        sids = np.full((self.total_cols,), self.num_segments, np.int32)
        for e in self.entries:
            for l in range(e.lead):
                lo = e.col_start + l * e.m_pad
                sids[lo : lo + e.m] = e.seg_start + l
        return sids

    def radii(self) -> np.ndarray:
        C = np.zeros((self.num_segments,), np.float32)
        for e in self.entries:
            C[e.seg_start : e.seg_start + e.lead] = e.radius
        return C

    def col_weights(self) -> np.ndarray:
        """Per-column weight vector of the packed buffer (1.0 on lane
        padding and on entries without spec weights) — the ``w_col`` input
        of weight-aware families; stacked matrices repeat their weights."""
        w = np.ones((self.total_cols,), np.float32)
        for e in self.entries:
            if e.weights is None:
                continue
            for l in range(e.lead):
                lo = e.col_start + l * e.m_pad
                w[lo : lo + e.m] = np.asarray(e.weights, np.float32)
        return w

    # -- virtual packing (the fused step, DESIGN.md §11) ---------------------
    # The fused train step never materializes the packed buffer: leaves keep
    # their own layout and only their O(m) per-column statistics are
    # concatenated, in entry order, with NO lane padding. These twins of
    # seg_ids()/col_weights() describe that dense layout.

    def virtual_num_cols(self) -> int:
        """Column count of the dense (un-lane-padded) statistics vector."""
        return sum(e.lead * e.m for e in self.entries)

    def virtual_seg_ids(self) -> np.ndarray:
        """Segment id per dense statistics column (entry order, stacked
        matrices contiguous, no padding sentinel — every column is real)."""
        parts = [np.repeat(np.arange(e.lead, dtype=np.int32) + e.seg_start,
                           e.m)
                 for e in self.entries]
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.int32))

    def virtual_col_weights(self) -> np.ndarray:
        """Per-column weights for the dense statistics layout (the
        ``w_col`` twin of :meth:`col_weights`)."""
        parts = []
        for e in self.entries:
            if e.weights is None:
                parts.append(np.ones((e.lead * e.m,), np.float32))
            else:
                parts.append(np.tile(np.asarray(e.weights, np.float32),
                                     e.lead))
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.float32))


def build_packed_plans(params: Any, specs: Sequence[ProjectionSpec]):
    """Split the leaves into packed plans — one per (constraint family,
    every_k) pair — and a per-leaf remainder [(leaf_index, spec)] for the
    unpackable balls (the l1 ball and seg_ops-less families like hoyer).

    ``params``: pytree of arrays or ShapeDtypeStructs (shapes are all that
    is read); ``specs``: ProjectionSpec sequence. Returns
    ``(plans, per_leaf)`` with ``plans`` a list of ``PackedPlan`` (static
    layout: lane-padded column blocks, per-column segment ids, per-segment
    radii). Pure shape bookkeeping — safe to call during tracing.

    >>> plans, per_leaf = build_packed_plans(params, specs)
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    groups: Dict[Tuple[str, int], list] = {}
    per_leaf = []
    for i, (path, leaf) in enumerate(flat):
        spec = _first_match(specs, leaf_path_str(path), leaf)
        if spec is None:
            continue
        fam = family_for_norm(spec.norm)
        if fam is not None and fam.seg_ops is not None:
            groups.setdefault((fam.name, spec.every_k), []).append(
                (i, leaf, spec))
        else:
            per_leaf.append((i, spec))

    plans = []
    for family, every_k in sorted(groups):
        col, seg, entries, n_max = 0, 0, [], 0
        for i, leaf, spec in groups[(family, every_k)]:
            shape = tuple(leaf.shape)
            lead = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
            n, m = shape[-2:]
            transpose = spec.axis in (1, -1)
            if transpose:
                n, m = m, n
            m_pad = -(-m // _LANE) * _LANE
            entries.append(_PackedEntry(
                index=i, shape=shape, lead=lead, n=n, m=m,
                transpose=transpose, radius=float(spec.radius),
                m_pad=m_pad, col_start=col, seg_start=seg,
                weights=spec.weights))
            col += lead * m_pad
            seg += lead
            n_max = max(n_max, n)
        n_max = -(-n_max // _SUBLANE) * _SUBLANE
        plans.append(PackedPlan(
            key=f"{family}_packed/k{every_k}", every_k=every_k, n_max=n_max,
            total_cols=col, num_segments=seg, entries=tuple(entries),
            family=family))
    return plans, per_leaf


def _pack_entry(x: jnp.ndarray, e: _PackedEntry, n_max: int) -> jnp.ndarray:
    """Leaf -> (n_max, lead * m_pad) canonical column block (f32)."""
    x2 = x.reshape((-1,) + x.shape[-2:]) if x.ndim > 2 else x[None]
    if e.transpose:
        x2 = jnp.swapaxes(x2, 1, 2)
    x2 = x2.astype(jnp.float32)
    x2 = jnp.pad(x2, ((0, 0), (0, n_max - e.n), (0, e.m_pad - e.m)))
    return jnp.moveaxis(x2, 0, 1).reshape(n_max, e.lead * e.m_pad)


def _unpack_entry(block: jnp.ndarray, e: _PackedEntry,
                  like: jnp.ndarray) -> jnp.ndarray:
    """(n_max, lead * m_pad) column block -> leaf with `like`'s shape/dtype."""
    x2 = jnp.moveaxis(block.reshape(block.shape[0], e.lead, e.m_pad), 1, 0)
    x2 = x2[:, : e.n, : e.m]
    if e.transpose:
        x2 = jnp.swapaxes(x2, 1, 2)
    return x2.reshape(like.shape).astype(like.dtype)


def _stacked_axis(axis: int, ndim: int) -> int:
    """Map a spec's max axis (defined on the trailing 2-D slice) to the
    corresponding axis of an ndim-rank stacked leaf. Negative axes already
    index from the trailing end, so they pass through unchanged; positive
    axes shift past the leading stack dims."""
    return axis if axis < 0 else axis + ndim - 2


def column_masks(params: Any, specs: Sequence[ProjectionSpec]) -> Any:
    """Per-leaf {0,1} masks from the current column support of matching leaves
    (the paper's double-descent mask M0). Non-matching leaves get ones.

    ``params``: pytree (constrained leaves >= 2-D); returns a pytree of the
    SAME structure/shapes/dtypes where each matching leaf holds 1.0 on
    columns with any nonzero entry (along the spec's max axis, per stacked
    slice for ndim > 2 leaves) and 0.0 on dead columns. The serving path
    (``sae/serve.support_selection``) derives its gather from this same
    mask, so training freeze and serving compaction cannot disagree.

    >>> masks = column_masks(params, (spec,))
    """
    def one(path, leaf):
        name = leaf_path_str(path)
        for spec in specs:
            if re.search(spec.pattern, name) and hasattr(leaf, "ndim") and leaf.ndim >= 2:
                nz = jnp.any(leaf != 0,
                             axis=_stacked_axis(spec.axis, leaf.ndim),
                             keepdims=True)
                return jnp.broadcast_to(nz, leaf.shape).astype(leaf.dtype)
        return jnp.ones_like(leaf)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def apply_masks(tree: Any, masks: Any) -> Any:
    """Elementwise tree * mask (grad masking of Algorithm 3).

    ``tree`` and ``masks``: pytrees of identical structure (broadcastable
    leaves — typically grads and the ``column_masks`` output). Returns the
    masked tree, dtypes following numpy promotion of ``t * m``.

    >>> grads = apply_masks(grads, masks)
    """
    return jax.tree_util.tree_map(lambda t, m: t * m, tree, masks)


def sparsity_report(params: Any, specs: Sequence[ProjectionSpec]) -> dict:
    """Column sparsity (%) per matching leaf — the paper's `Colsp` metric.

    Returns ``{leaf path: float percent}`` of fully-zero columns along the
    spec's max axis (stacked ndim > 2 leaves pool all slices). Host-side
    convenience (floats, not traced values) for logging and benches.

    >>> sparsity_report(params, (spec,))   # {'enc1/w': 99.0}
    """
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        name = leaf_path_str(path)
        for spec in specs:
            if re.search(spec.pattern, name) and hasattr(leaf, "ndim") and leaf.ndim >= 2:
                mat = leaf.reshape((-1,) + leaf.shape[-2:]) if leaf.ndim > 2 else leaf[None]
                dead = jnp.all(mat == 0, axis=_stacked_axis(spec.axis, 3))
                out[name] = float(100.0 * jnp.mean(dead.astype(jnp.float32)))
                break
    return out
