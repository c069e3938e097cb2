#!/usr/bin/env bash
# Tier-1 verification: collect must be clean, then the full suite on CPU.
#
#   scripts/check.sh               # docs check + collect check + full suite
#   scripts/check.sh --fast        # skip the slow subprocess multi-device tests
#   scripts/check.sh --bench-smoke # quick projection-engine benchmark gate:
#                                  # runs benchmarks/run.py --quick, emits
#                                  # BENCH_proj.json + BENCH_families.json +
#                                  # BENCH_dist_proj.json + BENCH_fused_step
#                                  # .json + BENCH_serve.json
#                                  # + BENCH_zoo_serve.json
#                                  # + BENCH_fleet_serve.json
#                                  # + BENCH_dist_fused.json (CI uploads all
#                                  # as artifacts), fails if the packed-batch
#                                  # path is >1.15x slower than per-matrix,
#                                  # the sharded engine is >1.15x the
#                                  # replicated solve on the 8-way host mesh,
#                                  # the bilevel family is >1.0x plain at the
#                                  # high-sparsity regime, the compacted SAE
#                                  # serving step costs >0.25x the dense
#                                  # encoder GEMM FLOPs at the ~99%
#                                  # column-sparsity regime, the zoo
#                                  # compact decode is <2x dense tokens/sec,
#                                  # not exact to 1e-4, or retraces across
#                                  # hot refresh / live re-compaction, the
#                                  # fused two-pass projected step is >0.8x
#                                  # the unfused one (wall time), touches
#                                  # more XLA-costed bytes, or diverges from
#                                  # the unfused params, or the fused_sharded
#                                  # step is >0.85x the unfused sharded one
#                                  # on the 8-way host mesh, gathers a weight
#                                  # shard, or diverges >1e-5 from it, or the
#                                  # continuous-batching fleet engine fails
#                                  # its gates (continuous < 2x cohort
#                                  # sustained tok/s under churn at the ~99%
#                                  # regime, any retrace across the
#                                  # admit/evict/refresh/recompact lifecycle,
#                                  # or any token mismatch vs dense / solo
#                                  # serving)
#
# The docs check (scripts/check_docs.py) enforces the public-API docstring
# contract (every exported symbol of the audited modules carries a
# docstring with a one-line example) and fails on stale DESIGN.md section
# anchors / broken local links referenced from docstrings and READMEs.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [[ "${1:-}" == "--bench-smoke" ]]; then
    echo "== bench smoke: projection engine (local + sharded) =="
    # benchmarks.run exits non-zero when a phase raises; removing the
    # artifacts first also guarantees the gate below reads THIS run's
    # numbers or fails loudly — never stale files
    rm -f BENCH_proj.json BENCH_families.json BENCH_dist_proj.json \
          BENCH_fused_step.json BENCH_serve.json BENCH_zoo_serve.json \
          BENCH_fleet_serve.json BENCH_dist_fused.json
    python -m benchmarks.run --quick --only proj_
    python -m benchmarks.run --quick --only dist_fused
    python -m benchmarks.run --quick --only fused_step
    python -m benchmarks.run --quick --only serve
    python -m benchmarks.run --quick --only zoo_serve
    python -m benchmarks.run --quick --only fleet_serve
    python - <<'PYEOF'
import json
d = json.load(open("BENCH_proj.json"))
ratio = d["packed"]["ratio_packed_vs_per_matrix"]
warm = d["warm_start"]["steady_state_newton_steps"]
diff = d["packed"]["max_abs_diff"]
assert ratio <= 1.15, (
    f"packed-batch path is {ratio:.2f}x the per-matrix time (>1.15x gate)")
assert diff <= 1e-4, f"packed != per-matrix (max abs diff {diff:.3e})"
# measured median is ~1.5-2; gate at 3 for fp/platform headroom (a broken
# warm start regresses to the cold ~5-8)
assert warm <= 3, f"steady-state warm Newton steps {warm} > 3"
print(f"bench smoke OK: packed/per-matrix {ratio:.2f}x, "
      f"steady-state warm Newton steps {warm}, packed max diff {diff:.2e}")

fd = json.load(open("BENCH_families.json"))
hi = [r for r in fd["regimes"] if r["C_frac"] == 0.01][0]
bratio = hi["ratio_bilevel_vs_plain"]
# the bi-level solve carries no per-column sort and O(m) iteration state —
# at high sparsity it must never lose to the exact solver. The 1.0 bound
# is not a zero-margin gate: measured ~0.02-0.07x on the quick CPU shape,
# so it holds >10x headroom against timing noise
assert bratio <= 1.0, (
    f"bilevel is {bratio:.2f}x plain at high sparsity (>1.0x gate)")
# the l1,2 solve (PR 10) is the same sort-free bilevel machinery on column
# energies — measured ~0.01x on the quick CPU shape, gated at 1.0 with the
# same >10x noise headroom as the bilevel gate above
lratio = hi["ratio_l12_vs_plain"]
assert lratio <= 1.0, (
    f"l12 is {lratio:.2f}x plain at high sparsity (>1.0x gate)")
assert fd["mixed"]["one_launch_per_family"], fd["mixed"]["launches"]
fdiff = fd["mixed"]["max_abs_diff_vs_per_leaf"]
assert fdiff <= 1e-4, f"mixed packed != per-leaf (max abs diff {fdiff:.3e})"
# the PR 10 fused l1,2 claim: the scale-mode two-pass fold rides the PR-7
# fused step unchanged — it must beat the unfused adam -> pack -> solve ->
# unpack step like the clip families do. Measured ~0.3x on the quick CPU
# shape, so the 0.85 gate keeps real headroom; exactness is gated tight
# (both solvers run the same Newton on the same energies — measured 0.0)
lf = fd["l12_fused"]
assert lf["ratio"] <= 0.85, (
    f"fused l12 step is {lf['ratio']:.3f}x the unfused step (>0.85x gate)")
assert lf["max_abs_diff"] <= 1e-5, (
    f"fused l12 != unfused params (max abs diff {lf['max_abs_diff']:.3e})")
print(f"families bench smoke OK: bilevel/plain {bratio:.2f}x, l12/plain "
      f"{lratio:.2f}x at high sparsity, one launch per family, mixed max "
      f"diff {fdiff:.2e}, fused l12 {lf['ratio']:.2f}x unfused")

dd = json.load(open("BENCH_dist_proj.json"))
dratio = dd["ratio_sharded_vs_replicated"]
ddiff = dd["max_abs_diff"]
ag = dd["collectives"]["sharded"]["all-gather"]
# measured ~0.3x on the 8-way host mesh; gate at 1.15 for platform headroom
assert dratio <= 1.15, (
    f"sharded engine is {dratio:.2f}x the replicated solve (>1.15x gate)")
assert ddiff <= 1e-4, f"sharded != replicated (max abs diff {ddiff:.3e})"
assert ag == 0, f"sharded projection HLO contains {ag} all-gather(s)"
print(f"dist bench smoke OK: sharded/replicated {dratio:.2f}x, "
      f"0 all-gathers, max diff {ddiff:.2e}")

sd = json.load(open("BENCH_serve.json"))
colsp = sd["regime"]["column_sparsity_pct"]
fratio = sd["flops"]["ratio_compact_vs_dense_encoder"]
sz = sd["exactness"]["max_abs_diff_z"]
sx = sd["exactness"]["max_abs_diff_xhat_on_support"]
# the paper's serving claim: at the ~99% column-sparsity regime the
# compacted encoder GEMM is ~0.01x the dense one. The 0.25 bound keeps
# ~25x headroom while still failing loudly if compaction silently stops
# dropping columns; the regime assertion keeps the gate honest (a bench
# that drifted to low sparsity would pass 0.25 vacuously)
assert colsp >= 95.0, f"serve bench regime drifted: colsp {colsp:.1f}% < 95%"
assert fratio <= 0.25, (
    f"compact encoder GEMM is {fratio:.3f}x dense (>0.25x gate)")
assert sz <= 1e-4 and sx <= 1e-4, (
    f"compact serve != dense on support (z {sz:.2e}, xhat {sx:.2e})")
print(f"serve bench smoke OK: colsp {colsp:.1f}%, compact/dense encoder "
      f"FLOPs {fratio:.4f}x, max diff {max(sz, sx):.2e}")

fsd = json.load(open("BENCH_fused_step.json"))
fs_ratio = fsd["worst_ratio"]
fs_bytes = fsd["worst_bytes_ratio"]
fs_diff = fsd["worst_abs_diff"]
# the PR-7 fused-step claim: the two-HBM-pass projected step (pass 1
# streams Adam + per-column stats, Newton on O(num_segments) state, pass 2
# recomputes and clip-writes; no physical packed buffer) beats the unfused
# adam -> pack -> solve -> unpack step at every sparsity regime. Measured
# ~0.4-0.6x on the quick CPU shape (the axis=1 decoder entry is where the
# packer's physical transpose hurts most), so the 0.8 gate keeps real
# headroom against timing noise. The bytes gate confirms the structural
# claim independently of the clock: the fused step's XLA-costed "bytes
# accessed" must be strictly below the unfused step's at every regime
# (measured ~0.64x). Exactness is gated bit-tight — both solvers run the
# same Newton on the same statistics, so the params must match to fp32
# roundoff, not just "close".
assert fs_ratio <= 0.8, (
    f"fused step is {fs_ratio:.3f}x the unfused step (>0.8x gate)")
assert fs_bytes is not None and fs_bytes < 1.0, (
    f"fused step bytes ratio {fs_bytes} not < 1.0 (two-pass claim broken)")
assert fs_diff <= 1e-5, f"fused != unfused params (max abs diff {fs_diff:.3e})"
print(f"fused step bench smoke OK: fused/unfused {fs_ratio:.2f}x wall, "
      f"{fs_bytes:.2f}x bytes, max diff {fs_diff:.2e}")

dfd = json.load(open("BENCH_dist_fused.json"))
df_ratio = dfd["ratio_fused_vs_sharded"]
df_diff = dfd["max_abs_diff"]
df_ag = dfd["collectives"]["fused_sharded"]["all-gather"]
# the PR-8 tentpole claim: the fused two-pass step run rank-local inside
# shard_map (no packed buffer, one stacked (2,G) f32 psum per Newton
# evaluation) beats the unfused sharded step (adam -> pack -> shard_map
# Newton -> unpack) on the same column-sharded inputs. Measured ~0.42-0.44x
# on the 8-way quick host mesh, so the 0.85 gate keeps ~2x headroom against
# timing noise. Exactness is gated tight: both solvers run the same Newton
# on the same per-column statistics (measured diff 0.0 — bit-identical fp
# order per rank), and no path may gather a weight shard.
assert df_ratio <= 0.85, (
    f"fused_sharded is {df_ratio:.3f}x the unfused sharded step "
    f"(>0.85x gate)")
assert df_diff <= 1e-5, (
    f"fused_sharded != sharded params (max abs diff {df_diff:.3e})")
assert df_ag == 0, (
    f"fused_sharded HLO contains {df_ag} all-gather(s)")
print(f"dist fused bench smoke OK: fused_sharded/sharded {df_ratio:.2f}x "
      f"wall, 0 all-gathers, max diff {df_diff:.2e}")

zd = json.load(open("BENCH_zoo_serve.json"))
zcolsp = zd["regime"]["column_sparsity_pct"]
speedup = zd["throughput"]["speedup_compact_vs_dense"]
zdiff = zd["exactness"]["max_abs_diff_logits"]
retr = zd["recompiles"]["extra_after_refresh_and_recompact"]
# the PR-6 zoo serving claim: at the ~99% column-sparsity regime the
# compact decode step (MLP-dominated shape) is >= 2x dense tokens/sec —
# measured ~5-7x on the quick CPU shape, so the 2x gate keeps headroom
# against timing noise; the regime assertion keeps it honest. Scatter-back
# is on the measured path, so the 1e-4 exactness gate covers it (measured
# ~1e-8: the gathered GEMMs sum the same nonzero terms). Hot refresh and
# live re-compaction are shape-preserving by the slot design — any extra
# trace is a contract violation, gated at exactly zero.
assert zcolsp >= 95.0, (
    f"zoo serve regime drifted: colsp {zcolsp:.1f}% < 95%")
assert speedup >= 2.0, (
    f"zoo compact decode is {speedup:.2f}x dense (<2x gate)")
assert zdiff <= 1e-4, f"zoo compact forward != dense ({zdiff:.3e})"
assert retr == 0, (
    f"{retr} retrace(s) across hot refresh + live re-compaction")
print(f"zoo serve bench smoke OK: colsp {zcolsp:.1f}%, compact "
      f"{speedup:.1f}x dense tok/s, max diff {zdiff:.2e}, 0 retraces")

fld = json.load(open("BENCH_fleet_serve.json"))
fcolsp = fld["regime"]["column_sparsity_pct"]
fspeed = fld["throughput"]["speedup_continuous_vs_cohort"]
fretr = fld["churn"]["extra_traces"]
fex = fld["exactness"]
# the PR-9 fleet serving claim: under open-loop churn (heavy-tailed
# generation lengths, one long request per cohort) continuous batching
# sustains >= 2x the cohort baseline's tok/s at the ~99% regime on the
# SAME compiled step — the cohort barrier idles finished slots (slot
# efficiency ~0.18 measured) while the engine re-admits them. Measured
# ~2.3-3x on the quick CPU shape. The lifecycle (mid-stream refresh +
# live recompact via the scheduler) must reuse the one trace, and every
# request's tokens must match dense and solo serving exactly (structural
# zeros + per-slot positions: bit-identical, gated at zero mismatches).
assert fcolsp >= 95.0, (
    f"fleet serve regime drifted: colsp {fcolsp:.1f}% < 95%")
assert fspeed >= 2.0, (
    f"continuous batching is {fspeed:.2f}x cohort tok/s (<2x gate)")
assert fretr == 0, (
    f"{fretr} retrace(s) across the admit/refresh/recompact lifecycle")
mism = (fex["token_mismatches_vs_dense"] + fex["token_mismatches_vs_solo"]
        + fex["token_mismatches_vs_cohort"])
assert mism == 0, f"{mism} token mismatch(es) across serving modes"
print(f"fleet serve bench smoke OK: colsp {fcolsp:.1f}%, continuous "
      f"{fspeed:.2f}x cohort tok/s, 0 retraces, 0 token mismatches")
PYEOF
    exit 0
fi

echo "== docs check (public-API docstrings + anchor targets) =="
python scripts/check_docs.py

echo "== collect check (must be 0 errors) =="
python -m pytest -q --collect-only >/dev/null

FAST_DESELECT=()
if [[ "${1:-}" == "--fast" ]]; then
    FAST_DESELECT=(--ignore=tests/test_multidevice.py
                   --ignore=tests/test_moe_and_serve.py
                   --ignore=tests/test_pipeline_compression.py)
fi

echo "== tier-1: pytest =="
# ${arr[@]+...} guard: empty-array expansion under `set -u` aborts on bash<4.4
python -m pytest -x -q ${FAST_DESELECT[@]+"${FAST_DESELECT[@]}"}
