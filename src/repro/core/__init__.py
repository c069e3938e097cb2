"""Core library: the paper's l1,inf projection family and its integration.

Public API:
    project_l1inf            — dispatcher (newton | sorted), jit/pjit-safe
    project_l1inf_newton     — semismooth Newton production path
    project_l1inf_sorted     — exact vectorized total order
    project_l1inf_heap       — faithful paper Algorithm 2 (CPU, numpy+heapq)
    project_l1inf_naive      — paper Algorithm 1
    project_l1inf_masked     — masked projection (Eq. 20)
    prox_linf1               — prox of the dual norm via Moreau (Eq. 16)
    project_l1_ball / project_l12_ball / project_simplex_sort
    project_l1inf_segmented  — packed multi-ball solve (one sweep per group)
    support_indices / compact_columns — host-side support gather: the
        serving-time column-compaction primitives (``repro.sae.serve``)
    project_l1inf_segmented_sharded — shard_map twin (psum per iteration)
    project_bilevel          — bi-level l1,inf operator (arXiv:2407.16293),
        linear-time; project_bilevel_ref is its sort-based exact reference
    project_l12_newton       — l1,2 (group-lasso) ball via the segmented
        Newton on column energies (fuses: DESIGN.md §14)
    project_hoyer            — Hoyer sparseness-ratio projection
        (arXiv:1303.5259); project_hoyer_ref is its sorted closed form,
        hoyer_sparseness the per-column sigma diagnostic
    ConstraintFamily / register_family / get_family / family_for_norm —
        the pluggable constraint-family registry (core.families): every
        family rides the packed / Pallas / sharded engine machinery
    project_segmented_family / project_segmented_family_sharded —
        family-dispatching packed solves
    ProjectionSpec / apply_constraints / column_masks — training integration
    ProjectionEngine         — plan building + theta state + solver dispatch
        (newton | pallas | sharded) + the projected_update step core every
        train loop builds on; one packed solve per (family, every_k)
    apply_constraints_packed / init_projection_state  — functional shims
        over the engine (packed batching with warm-started Newton)
    newton_evals             — Eq.-(19) evaluations of one projected update
    engine_counters / engine_counters_reset — solver-invocation accounting
        (the registry of ``repro.obs``)
"""
from .simplex import (project_simplex_sort, project_l1_ball,
                      project_weighted_l1_ball, simplex_threshold)
from .l1inf import (l1inf_norm, project_l1inf, project_l1inf_sorted,
                    project_l1inf_newton, project_l1inf_newton_stats,
                    project_l1inf_segmented, project_l1inf_segmented_sharded,
                    theta_l1inf, column_support, active_compaction,
                    support_indices, compact_columns)
from .heap import project_l1inf_heap, project_l1inf_naive, theta_l1inf_heap
from .baselines import (project_l1inf_quattoni, project_l1inf_bejar,
                        project_l1inf_newton_np)
from .norms import project_l12_ball, prox_linf1, linf1_norm, l12_norm
from .masked import project_l1inf_masked, l1inf_column_mask
from .weighted import project_l1inf_weighted, l1inf_weighted_norm
from .bilevel import (project_bilevel, project_bilevel_stats,
                      project_bilevel_ref, bilevel_norm)
from .l12 import project_l12_newton, project_l12_stats
from .hoyer import hoyer_sparseness, project_hoyer, project_hoyer_ref
from .families import (ConstraintFamily, register_family, get_family,
                       family_for_norm, family_names, packable_norms,
                       registered_norms, project_segmented_family,
                       project_segmented_family_sharded)
from .constraints import (ProjectionSpec, apply_constraints,
                          build_packed_plans, column_masks, apply_masks,
                          sparsity_report, engine_counters,
                          engine_counters_reset)
from .engine import (ProjectionEngine, apply_constraints_packed,
                     init_projection_state, newton_evals)
