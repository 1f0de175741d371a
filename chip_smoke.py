"""Run the BTARD training path once on a TPU, at albert-large's full width.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # one host with four chips

One chip runs two phases, each through an entry point the README documents:

* launch — ``repro.launch.train.main`` (``python -m repro.launch.train``) on a
  1x1 mesh at seq 512, with ``--use-pallas`` and without. Both final losses
  are finite and agree, and the Pallas run's lowered step holds a native
  kernel (``tpu_custom_call``).
* engine — ``BTARDTrainer.run_scan`` over ``lm_setup("albert_large",
  reduced=False)``, the code ``examples/train_byzantine.py --model
  albert_large --full`` runs: 4 simulated peers on the chip, peer 3
  sign-flipping from step 0, with and without Pallas. Peer 3 is banned
  within 5 steps at the same step in both runs, and no honest peer is
  accused.

``--four-chips`` runs only the path across chips: the launch CLI on a 4x1 mesh
(4 peers, one chip each). Honest BTARD with the clip inactive
(``butterfly_clip:tau=1e9``) must match the ``--defense mean`` baseline's
final loss, and a sign-flipping peer 1 must be banned under
``butterfly_clip`` and under ``compressed:verified:mean``.

Each phase prints one informational JSON line (not a benchmark). The last
line is ``{"ok": true, "device": {...}}``. With no TPU the script exits
non-zero before any phase and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
IR_DIR = ROOT / ".smoke_ir"  # git-ignored; holds lowered modules briefly
LOSS_RTOL = 1e-3  # Pallas vs jnp launch loss (same arithmetic, f32 order)
MEAN_RTOL = 5e-3  # honest BTARD (clip inactive) vs the mean baseline


def _device():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


@contextlib.contextmanager
def _lowered_modules():
    """Collect the text of every module jax lowers inside the block."""
    import jax

    texts = []
    shutil.rmtree(IR_DIR, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", str(IR_DIR))
    try:
        yield texts
    finally:
        jax.config.update("jax_dump_ir_to", "")
        texts += [p.read_text() for p in sorted(IR_DIR.glob("*.mlir"))]
        shutil.rmtree(IR_DIR, ignore_errors=True)


def _train(argv):
    """One in-process launch CLI run; returns (summary, seconds)."""
    from repro.launch import train

    t0 = time.time()
    summary = train.main(argv)
    return summary, time.time() - t0


def _emit(record):
    print(json.dumps(record), flush=True)


def _require(ok, what):
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def phase_launch(arch="albert-large", *, reduced=False, seq=512, batch=8,
                 steps=4, scan_steps=2, clip_iters=20):
    """Launch CLI on one device, with and without Pallas. Returns the two
    final losses and whether the Pallas step held a native kernel."""
    import jax

    argv = ["--arch", arch, "--mesh", "1x1", "--seq", str(seq),
            "--batch", str(batch), "--steps", str(steps),
            "--scan-steps", str(scan_steps), "--clip-iters", str(clip_iters)]
    if reduced:
        argv.append("--reduced")
    losses = {}
    for pallas in (True, False):
        if pallas:
            with _lowered_modules() as texts:
                summary, secs = _train(argv + ["--use-pallas"])
            native = any("tpu_custom_call" in t for t in texts)
        else:
            summary, secs = _train(argv)
        losses[pallas] = summary["final_loss"]
        _emit({"phase": "launch", "pallas": pallas, "arch": arch,
               "seq": seq, "batch": batch, "steps": summary["steps_done"],
               "final_loss": summary["final_loss"], "wall_s": secs,
               "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
               "device_kind": jax.devices()[0].device_kind})
    _require(all(math.isfinite(v) for v in losses.values()),
             f"finite launch losses {losses}")
    _require(math.isclose(losses[True], losses[False], rel_tol=LOSS_RTOL),
             f"Pallas and jnp launch losses agree {losses}")
    return {"loss_pallas": losses[True], "loss_jnp": losses[False],
            "native_kernel": native}


def phase_engine(arch="albert_large", *, reduced=False, seq=512, batch=2,
                 steps=6, n_peers=4, clip_iters=5):
    """Scanned engine with n simulated peers, the last one sign-flipping
    from step 0. Returns the ban step of each banned peer per run."""
    import jax

    from repro.core import AttackConfig, BTARDTrainer, TrainerConfig
    from repro.models.workload import lm_setup
    from repro.optim import sgd

    loss_fn, params0, batch_fn, _ = lm_setup(
        arch, seq_len=seq, batch_size=batch, reduced=reduced)
    byz = n_peers - 1
    bans = {}
    for pallas in (True, False):
        cfg = TrainerConfig(
            n_peers=n_peers, byzantine=(byz,),
            attack=AttackConfig(kind="sign_flip", start_step=0, delay=5),
            aggregator="butterfly_clip", tau=1.0, clip_iters=clip_iters,
            m_validators=2, use_pallas=pallas)
        tr = BTARDTrainer(loss_fn, params0, batch_fn, cfg,
                          optimizer=sgd(0.05))
        t0 = time.time()
        tr.run_scan(steps)
        secs = time.time() - t0
        ban_step = {}
        accused = set()
        for rec in tr.history:
            for p, _ in rec["banned_now"]:
                ban_step.setdefault(p, rec["step"])
            accused |= set(rec["accused_peers"])
        honest_accused = sorted(accused - {byz})
        bans[pallas] = ban_step
        _emit({"phase": "engine", "pallas": pallas, "arch": arch,
               "d": tr.d, "n_peers": n_peers, "seq": seq, "batch": batch,
               "steps": steps, "ban_steps": ban_step,
               "honest_accused": honest_accused,
               "final_grad_norm": tr.history[-1]["grad_norm"],
               "wall_s": secs,
               "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
               "device_kind": jax.devices()[0].device_kind})
        _require(set(ban_step) == {byz} and ban_step[byz] <= 4,
                 f"peer {byz} alone banned within 5 steps {ban_step}")
        _require(not honest_accused, f"no honest accusation {honest_accused}")
    _require(bans[True] == bans[False], f"equal ban steps {bans}")
    return bans


def phase_four_chips(arch="albert-large", *, reduced=False, seq=512,
                     batch=8, steps=4, scan_steps=2, clip_iters=20):
    """The launch CLI on a 4x1 mesh: one peer per device."""
    import jax

    base = ["--arch", arch, "--mesh", "4x1", "--seq", str(seq),
            "--batch", str(batch), "--steps", str(steps),
            "--scan-steps", str(scan_steps), "--clip-iters", str(clip_iters),
            "--use-pallas"]
    if reduced:
        base.append("--reduced")
    attack = ["--attack", "sign_flip", "--byzantine", "1"]
    runs = {
        "honest_btard": ["--aggregator", "butterfly_clip:tau=1e9"],
        "mean": ["--defense", "mean"],
        "sign_flip_butterfly_clip": attack,
        "sign_flip_compressed": attack + [
            "--aggregator", "compressed:verified:mean"],
    }
    out = {}
    for name, extra in runs.items():
        summary, secs = _train(base + extra)
        out[name] = summary
        _emit({"phase": "four_chips", "run": name, "arch": arch,
               "final_loss": summary["final_loss"],
               "banned_slots": summary["banned_slots"], "wall_s": secs,
               "peak_bytes_in_use": [_peak_bytes(d) for d in jax.devices()],
               "device_kind": jax.devices()[0].device_kind})
    a, b = out["honest_btard"]["final_loss"], out["mean"]["final_loss"]
    _require(math.isfinite(a) and math.isclose(a, b, rel_tol=MEAN_RTOL),
             f"honest BTARD loss {a} matches the mean baseline {b}")
    _require(out["honest_btard"]["banned_slots"] == [],
             f"no honest ban {out['honest_btard']}")
    for name in ("sign_flip_butterfly_clip", "sign_flip_compressed"):
        _require(out[name]["banned_slots"] == [1],
                 f"{name} bans slot 1 alone {out[name]}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-peer launch path on four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev['platform']})",
              file=sys.stderr)
        return 1
    if args.four_chips:
        if dev["count"] < 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found "
                  f"{dev['count']}", file=sys.stderr)
            return 1
        phase_four_chips()
        _require(all(_peak_bytes(d) for d in jax.devices()),
                 "every device held state")
    else:
        launch = phase_launch()
        _require(launch["native_kernel"],
                 "the Pallas step holds a native kernel (tpu_custom_call)")
        phase_engine()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
