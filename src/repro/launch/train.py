"""Training launcher.

CPU-scale real runs (examples, CI) and chip launches share this entry
point. The mesh spans every visible device as a data axis, so the params,
optimizer state and batches are laid out over all attached chips and the
projection takes the engine ``launch.steps.projection_engine_for`` picks
for that mesh. ``main(argv)`` returns the training result, so a caller can
drive it in its own process.

    python -m repro.launch.train --arch gemma_7b --reduced --steps 200
    python -m repro.launch.train --arch mamba2_370m --reduced --resume auto
"""
from __future__ import annotations

import argparse

import jax

from ..configs import ARCH_IDS, get_config, get_reduced
from ..dist.sharding import default_rules
from ..models.zoo import build
from ..data.pipeline import SyntheticLM, LMBatcher
from ..train.loop import TrainConfig, train
from .cache import enable_compile_cache
from .mesh import make_local_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--no-projection", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build(cfg)
    print(f"[launch] {cfg.name}: {model.n_params()/1e6:.1f}M params, "
          f"{len(jax.devices())} device(s)")

    batcher = LMBatcher(SyntheticLM(cfg.vocab), args.batch, args.seq)
    tcfg = TrainConfig(steps=args.steps, lr=args.lr,
                       microbatches=args.microbatches,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       with_projection=not args.no_projection)
    mesh = make_local_mesh(data=len(jax.devices()))
    out = train(model, batcher, tcfg, mesh=mesh, rules=default_rules(),
                resume=(args.resume == "auto"))
    print(f"[launch] final loss {out['losses'][-1]:.4f}; "
          f"first loss {out['losses'][0]:.4f}")
    wd = out["watchdog"]
    print(f"[launch] step time EWMA {wd['step_time_ewma_s']*1e3:.0f} ms; "
          f"{int(wd['straggler_events_total'])} straggler step(s)")
    for s, dt, ew in out["straggler_events"][:5]:
        print(f"[launch]   straggler step {s}: {dt:.3f}s "
              f"(EWMA was {ew:.3f}s)")
    if out["sparsity"]:
        for k, v in out["sparsity"].items():
            print(f"[sparsity] {k}: {v:.1f}% columns zero")
    return out


if __name__ == "__main__":
    main()
