"""Paper §4.1-style controlled experiment: pick an attack and a defense,
watch the bans and the accuracy trajectory.

  PYTHONPATH=src python examples/train_byzantine.py --attack alie --defense btard
  PYTHONPATH=src python examples/train_byzantine.py --attack sign_flip --defense mean

The default workload is the toy gaussian-mixture classifier. ``--model``
swaps in a real LM from the config registry (the §4.2-style setup) and runs
the SCANNED engine — per-peer gradients from ``Model.loss_fn``, flattened at
the core.flatten ravel boundary, any registered aggregator on the wire:

  PYTHONPATH=src python examples/train_byzantine.py --model albert_large \\
      --aggregator compressed:verified:mean --attack sign_flip --steps 6
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core import AttackConfig, BTARDTrainer, TrainerConfig
from repro.optim import sgd


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--attack", default="sign_flip",
                    choices=["none", "sign_flip", "random_direction", "label_flip",
                             "delayed_gradient", "ipm_01", "ipm_06", "alie"])
    ap.add_argument("--defense", default="btard",
                    choices=["btard", "mean", "coordinate_median",
                             "geometric_median", "trimmed_mean", "krum",
                             "centered_clip"])
    ap.add_argument("--peers", type=int, default=None,
                    help="default: 16 (toy) / 4 (--model)")
    ap.add_argument("--byzantine", type=int, default=None,
                    help="default: 7 (toy) / 1 (--model)")
    ap.add_argument("--steps", type=int, default=None,
                    help="default: 60 (toy) / 6 (--model)")
    ap.add_argument("--attack-start", type=int, default=None,
                    help="default: 10 (toy) / 0 (--model)")
    ap.add_argument("--tau", type=float, default=1.0)
    ap.add_argument("--validators", type=int, default=2)
    # ------------------------------------------------- real-model gauntlet
    ap.add_argument("--model", default=None, metavar="ARCH",
                    help="train a zoo LM (e.g. albert_large, qwen3-1.7b) "
                         "through the scanned BTARD engine instead of the "
                         "toy classifier")
    ap.add_argument("--aggregator", default=None,
                    help="AggregatorSpec string for the engine path, e.g. "
                         "compressed:verified:mean (overrides --defense)")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced smoke variant)")
    ap.add_argument("--dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="override param/activation storage dtype")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--clip-iters", type=int, default=None,
                    help="CenteredClip iteration budget (default 60 toy / 5 model)")
    return ap


def run_model(args):
    """Scanned BTARD over a real LM; prints a SUMMARY json line."""
    from repro.models.workload import lm_setup

    peers = args.peers or 4
    n_byz = 1 if args.byzantine is None else args.byzantine
    steps = args.steps or 6
    loss_fn, params0, batch_fn, model = lm_setup(
        args.model, seq_len=args.seq, batch_size=args.batch,
        reduced=not args.full, dtype=args.dtype,
    )
    cfg = TrainerConfig(
        n_peers=peers,
        byzantine=tuple(range(peers - n_byz, peers)),
        attack=AttackConfig(
            kind=args.attack,
            start_step=args.attack_start or 0,
            delay=5,
        ),
        defense=args.defense if args.aggregator is None else "btard",
        aggregator=args.aggregator,
        tau=args.tau,
        clip_iters=args.clip_iters or 5,
        m_validators=args.validators,
    )
    tr = BTARDTrainer(loss_fn, params0, batch_fn, cfg, optimizer=sgd(0.05))
    print(f"model={model.cfg.name} d={tr.d} peers={peers} byz={n_byz} "
          f"aggregator={args.aggregator or args.defense} dtype={model.cfg.dtype}")
    tr.run_scan(steps)
    byz = set(cfg.byzantine)
    ban_steps = {}
    honest_accused = set()
    for rec in tr.history:
        print(f"step {rec['step']:3d}  |g|={rec['grad_norm']:10.4f}  "
              f"banned={rec['n_banned']}"
              + (f"  BANNED {rec['banned_now']}" if rec["banned_now"] else ""))
        for p, _ in rec["banned_now"]:
            ban_steps.setdefault(p, rec["step"])
        honest_accused |= set(rec.get("accused_peers", [])) - byz
    summary = {
        "model": model.cfg.name,
        "d": tr.d,
        "dtype": model.cfg.dtype,
        "aggregator": args.aggregator or args.defense,
        "attack": args.attack,
        "steps": steps,
        "byzantine": sorted(byz),
        "banned": sorted(tr.banned),
        "ban_steps": ban_steps,
        "honest_accused": sorted(honest_accused),
        "final_grad_norm": tr.history[-1]["grad_norm"],
    }
    print("SUMMARY " + json.dumps(summary))


def run_toy(args):
    from benchmarks.common import classification_setup

    peers = args.peers or 16
    n_byz = 7 if args.byzantine is None else args.byzantine
    loss_fn, params0, batch_fn, accuracy = classification_setup()
    cfg = TrainerConfig(
        n_peers=peers,
        byzantine=tuple(range(peers - n_byz, peers)),
        attack=AttackConfig(
            kind=args.attack,
            start_step=10 if args.attack_start is None else args.attack_start,
            delay=5,
        ),
        defense=args.defense,
        aggregator=args.aggregator,
        tau=args.tau,
        clip_iters=args.clip_iters or 60,
        m_validators=args.validators,
    )
    tr = BTARDTrainer(loss_fn, params0, batch_fn, cfg,
                      optimizer=sgd(0.3, momentum=0.9))

    def log(rec):
        if rec["step"] % 5 == 0 or rec.get("banned_now"):
            acc = accuracy(tr.unraveled_params())
            extra = f" BANNED {rec['banned_now']}" if rec.get("banned_now") else ""
            print(f"step {rec['step']:3d}  acc={acc:.3f}  "
                  f"banned={rec['n_banned']}/{n_byz}{extra}")

    tr.run(args.steps or 60, log=log)
    print(f"\nfinal accuracy: {accuracy(tr.unraveled_params()):.3f}")
    print(f"banned peers  : {sorted(tr.banned)}")


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.model:
        run_model(args)
    else:
        run_toy(args)


if __name__ == "__main__":
    main()
