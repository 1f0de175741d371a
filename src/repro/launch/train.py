"""End-to-end distributed training driver.

Runs the BTARD (or baseline AR-SGD) train step on whatever devices exist —
the production mesh shape is requested via --mesh, host devices via
--host-devices for CPU bring-up. Data comes from the deterministic
public-seed pipeline; checkpoints via repro.checkpoint.

Examples (CPU bring-up, 8 fake devices):
  python -m repro.launch.train --arch qwen3-1.7b --reduced \\
      --host-devices 8 --mesh 4x2 --steps 20 --defense btard
  python -m repro.launch.train --arch mamba2-2.7b --reduced --host-devices 8 \\
      --mesh 4x2 --steps 10 --attack sign_flip --byzantine 1,3
  # device-resident scan loop: 5 rounds per compiled dispatch, batches
  # generated IN-SCAN from the public seed chain, warm-started CenteredClip
  # with the adaptive early-exit budget
  python -m repro.launch.train --arch qwen3-1.7b --reduced --host-devices 8 \\
      --mesh 4x2 --steps 20 --scan-steps 5 \\
      --aggregator butterfly_clip:warm_start=true,adaptive_tol=1e-4
  # swap the robust aggregator (paper Fig. 3 comparison axis): any
  # registered AggregatorSpec name, with optional static params
  python -m repro.launch.train --arch qwen3-1.7b --reduced --host-devices 8 \\
      --mesh 4x2 --steps 10 --scan-steps 5 --attack sign_flip \\
      --byzantine 1,3 --aggregator krum
  # compressed wire: int8 butterfly payloads + f32 scale sidecars, digests
  # over the dequantized wire values (verification stays exact)
  python -m repro.launch.train --arch qwen3-1.7b --reduced --host-devices 4 \\
      --mesh 2x2 --steps 8 --scan-steps 4 --attack sign_flip --byzantine 1 \\
      --aggregator compressed:verified:mean
"""
import argparse
import os
import time
import warnings


def resolve_cli_aggregator(text, warm_start_clip=False, adaptive_clip=None,
                           n_byzantine=0):
    """Parse ``--aggregator NAME[:k=v,...]`` and fold the DEPRECATED
    ``--warm-start-clip`` / ``--adaptive-clip TOL`` flags into the spec
    (they keep working as aliases for the equivalent spec params).
    Krum's ``n_byzantine`` defaults to the --byzantine list length."""
    from repro.core.aggregators import AggregatorSpec, with_byzantine_default

    spec = AggregatorSpec.parse(text)
    shims = {}
    if warm_start_clip:
        warnings.warn(
            "--warm-start-clip is deprecated; use "
            "--aggregator butterfly_clip:warm_start=true",
            DeprecationWarning, stacklevel=2,
        )
        shims["warm_start"] = True
    if adaptive_clip is not None:
        warnings.warn(
            "--adaptive-clip is deprecated; use "
            f"--aggregator butterfly_clip:adaptive_tol={adaptive_clip}",
            DeprecationWarning, stacklevel=2,
        )
        shims["adaptive_tol"] = adaptive_clip
    if shims:
        accepted = set(spec.definition.param_names)
        dropped = [k for k in shims if k not in accepted]
        if dropped:
            warnings.warn(
                f"aggregator {spec.name!r} takes no {dropped}; the "
                "deprecated clip flags only apply to warm-startable/"
                "adaptive specs and are ignored here",
                stacklevel=2,
            )
        spec = spec.override(
            **{k: v for k, v in shims.items() if k in accepted}
        )
    return with_byzantine_default(spec, n_byzantine)


def main(argv=None):
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``) and return the
    SUMMARY dict it prints last."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--mesh", default="4x2", help="DATAxMODEL or PODxDATAxMODEL")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--defense", default="btard", choices=["btard", "mean"])
    ap.add_argument("--tau", type=float, default=2.0)
    ap.add_argument("--clip-iters", type=int, default=20)
    ap.add_argument("--attack", default="none",
                    choices=["none", "sign_flip", "random_direction", "ipm"])
    ap.add_argument("--byzantine", default="", help="comma-separated peer idxs")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--scan-steps", type=int, default=0,
                    help="BTARD rounds per jitted lax.scan dispatch "
                         "(0 = one dispatch per round)")
    ap.add_argument("--aggregator", default="butterfly_clip",
                    metavar="NAME[:k=v,...]",
                    help="robust aggregator spec for the btard defense: "
                         "butterfly_clip (verifiable flagship; params tau, "
                         "n_iters, warm_start, adaptive_tol), mean, "
                         "coordinate_median, trimmed_mean[:trim_ratio=R], "
                         "geometric_median, krum[:n_byzantine=B], "
                         "centered_clip[:tau=T]. verified:BASE[:k=v,...] "
                         "lifts a coordinatewise baseline (mean, "
                         "trimmed_mean, coordinate_median) into a "
                         "verifiable one: butterfly all_to_all topology + "
                         "recomputable contribution digests instead of the "
                         "O(n*d) PS all_gather (e.g. "
                         "verified:trimmed_mean:trim_ratio=0.2). "
                         "compressed:SPEC[:codec=int8|bf16] quantizes the "
                         "butterfly all_to_all payloads (int8: ~4x fewer "
                         "wire bytes + one f32 scale sidecar per payload; "
                         "default codec int8) with every digest computed "
                         "over the dequantized wire values, so "
                         "verification stays exact (e.g. "
                         "compressed:verified:mean, "
                         "compressed:butterfly_clip:codec=bf16). "
                         "Non-verifiable specs run without the "
                         "verification/ban machinery. --tau and "
                         "--clip-iters fill the spec's defaults; explicit "
                         "spec params win.")
    ap.add_argument("--groups", type=int, default=0,
                    help="hierarchical butterfly-of-butterflies: split the "
                         "peer axis into GROUPS groups of n/GROUPS; level-1 "
                         "butterfly within each group (per-peer table "
                         "traffic O((n/g)^2) instead of O(n^2)), level-2 "
                         "active-weight mean of the group aggregates "
                         "(exact linear checksum). Verifiable specs only; "
                         "GROUPS must divide the peer count with >= 2 "
                         "members per group. 0 = flat (default)")
    ap.add_argument("--audit-k", type=int, default=0,
                    help="sampled-digest verification: only K owner "
                         "columns per step (a rotating seed-driven window) "
                         "broadcast their digests — table bytes drop "
                         "n^2 -> n*K while every column is audited within "
                         "n/K steps. Composes with --groups (the window "
                         "rotates within each group). 0 = every column "
                         "every step (default)")
    ap.add_argument("--agg-attack", type=float, default=0.0, metavar="SCALE",
                    help="simulate the LYING AGGREGATOR: Byzantine peers "
                         "(--byzantine) corrupt their owned partition "
                         "aggregate by SCALE x rms after aggregating and "
                         "report self-consistent digests; detection is via "
                         "the V2 checksum (linear specs) or the validator "
                         "audit arm (any verifiable spec). 0 = off")
    ap.add_argument("--warm-start-clip", action="store_true",
                    help="DEPRECATED alias for "
                         "--aggregator butterfly_clip:warm_start=true "
                         "(implies the scan step; see kernels/DESIGN.md)")
    ap.add_argument("--adaptive-clip", type=float, default=None, metavar="TOL",
                    help="DEPRECATED alias for "
                         "--aggregator butterfly_clip:adaptive_tol=TOL "
                         "(--clip-iters becomes the static cap)")
    ap.add_argument("--host-data", action="store_true",
                    help="feed host-precomputed batches to the scan step "
                         "instead of generating them in-scan on device "
                         "(the default scan path is fully device-resident)")
    ap.add_argument("--churn", default="", metavar="EVENTS",
                    help="elastic-membership schedule: comma-separated "
                         "KIND@STEP:SLOT events (kind join|leave), e.g. "
                         "'leave@6:1,join@8:1'. A leave vacates the slot; a "
                         "join puts a FRESH identity into a vacant slot "
                         "under probation — it computes public-seed "
                         "gradients spot-checked every step (the "
                         "probe_mismatch audit arm) and only a clean "
                         "--probation-steps window admits it to the "
                         "aggregate. Identity ban ledgers survive churn: a "
                         "banned slot that leaves and rejoins is re-vetted, "
                         "and re-banned the moment it misbehaves, without "
                         "ever re-entering the aggregate")
    ap.add_argument("--probation-steps", type=int, default=3,
                    help="consecutive clean spot-checks a joining peer "
                         "needs before its slot turns active (default 3)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="directory for crash-recovery checkpoints: "
                         "params + optimizer + warm-start carry + the full "
                         "membership/ban ledger are saved at every scan-"
                         "chunk boundary (atomic), so a killed run resumes "
                         "bitwise with --resume. Requires --scan-steps")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the checkpoint in --checkpoint-dir "
                         "(same CLI config required); continues at the "
                         "saved chunk boundary with identical bans and "
                         "aggregates (scan-resume bitwise property)")
    ap.add_argument("--halt-at", type=int, default=None, metavar="STEP",
                    help="crash drill: exit right after the first chunk-"
                         "boundary checkpoint at or beyond STEP (pair with "
                         "--resume to verify recovery)")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--trace-dir", default="", metavar="DIR",
                    help="write a profiler trace of the second and third "
                         "chunk (compiled by then) under DIR: the device's "
                         "ops by phase scope and the btard.host.* spans; "
                         "without --scan-steps a chunk is one step")
    args = ap.parse_args(argv)

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    byz = set(int(x) for x in args.byzantine.split(",") if x)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.configs.base import InputShape
    from repro.core import butterfly as bf
    from repro.core.sybil import HostMembership, parse_churn
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import (
        make_baseline_train_step,
        make_btard_scan_train_step,
        make_btard_train_step,
    )
    from repro.models import get_model
    from repro.optim import sgd
    from repro.sharding import set_mesh
    from repro.sharding.specs import set_seq_parallel

    dims = [int(x) for x in args.mesh.split("x")]
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    mesh = make_mesh(dims, names)
    set_mesh(mesh)
    set_seq_parallel(args.seq_parallel)

    model = get_model(args.arch, reduced=args.reduced)
    shape = InputShape("cli", args.seq, args.batch, "train")
    opt = sgd(args.lr, momentum=0.9, nesterov=True)
    n_peers = int(np.prod([mesh.shape[a] for a in names if a != "model"]))

    agg_spec = resolve_cli_aggregator(
        args.aggregator, args.warm_start_clip, args.adaptive_clip, len(byz)
    )
    warm = bool(agg_spec.warm_startable and agg_spec.get("warm_start", False))

    extras = None
    if model.cfg.encoder_len:
        extras = {
            "memory_raw": ((model.cfg.encoder_len, model.cfg.encoder_dim), jnp.float32)
        }
    pipe = TokenPipeline(model.cfg.vocab_size, args.seq, args.batch)

    n_scan = max(args.scan_steps, 1 if warm else 0)
    # the scan path is device-resident by default: batches come from the
    # public peer_key chain INSIDE the compiled scan (same bits as the host
    # pipeline), so each dispatch moves only two (n_scan,) i32 vectors
    device_data = bool(n_scan) and not args.host_data
    flat_cost = dict(
        groups=args.groups or None, audit_k=args.audit_k or None,
        agg_attack=args.agg_attack or None,
    )
    if args.defense == "btard" and n_scan:
        step_fn, _ = make_btard_scan_train_step(
            model, opt, mesh, shape, n_scan_steps=n_scan, tau=args.tau,
            clip_iters=args.clip_iters, attack=args.attack,
            use_pallas=args.use_pallas, aggregator=agg_spec,
            pipeline=pipe if device_data else None, extras=extras,
            **flat_cost,
        )
    elif args.defense == "btard":
        step_fn, _ = make_btard_train_step(
            model, opt, mesh, shape, tau=args.tau, clip_iters=args.clip_iters,
            attack=args.attack, use_pallas=args.use_pallas,
            aggregator=agg_spec, **flat_cost,
        )
    else:
        step_fn, _ = make_baseline_train_step(model, opt, mesh, shape)

    params = model.init_params(jax.random.key(0))
    opt_state = opt.init(params)

    byz_mask = jnp.asarray(
        [1.0 if i in byz else 0.0 for i in range(n_peers)], jnp.float32
    )
    # every peer starts active — even the Byzantine ones; bans flow from the
    # verification checksums below, never from out-of-band knowledge. The
    # membership ledger (core.sybil.HostMembership) owns the slot lifecycle:
    # --churn events toggle slots between dispatches, the probe_mismatch
    # audit arm drives probation spot-checks, and bans are keyed by IDENTITY
    # so a leave/rejoin can never launder them.
    mem = HostMembership(
        n_peers, probation_steps=args.probation_steps,
        events=parse_churn(args.churn) if args.churn else None,
    )
    weights = jnp.asarray(mem.weights())

    def apply_bans(weights, step, *offender_sets):
        newly = mem.ban_slots(
            {int(b) for s in offender_sets for b in s}, step
        )
        if newly:
            print(f"banned peers -> {mem.banned_slots()}", flush=True)
        return jnp.asarray(mem.weights())

    def audit_offenders(verif, tol=1e-5):
        """Peers whose validator audit (gradient recompute or partition-
        aggregation recompute — steps.aggregation_stage) deviated from
        their broadcast payloads. Honest peers report EXACT zeros (the
        recompute is bit-identical), so any excess over float tolerance is
        a lie; works for every verifiable spec, including the nonlinear
        verified:* wrappers whose digests carry no zero-sum checksum."""
        bad = set()
        for k in ("audit_grad_mismatch", "audit_agg_mismatch"):
            if isinstance(verif, dict) and k in verif:
                a = np.asarray(verif[k], np.float64)
                if a.ndim > 1:  # scan mode: catch mid-chunk audits too
                    a = a.max(0)
                bad |= {int(i) for i in np.nonzero(a > tol)[0]}
        return bad

    if args.churn and not n_scan:
        # per-step mode applies events/probes too, but the CI-proven path
        # (and the checkpointed one) is the scan loop — keep configs honest
        print("note: --churn granularity is per step in non-scan mode")
    if (args.checkpoint_dir or args.resume) and not n_scan:
        ap.error("--checkpoint-dir/--resume require --scan-steps "
                 "(checkpoints are cut at scan-chunk boundaries)")
    if args.halt_at is not None and not args.checkpoint_dir:
        ap.error("--halt-at exits after a boundary checkpoint, so it "
                 "requires --checkpoint-dir")

    print(f"arch={model.cfg.name} params={model.param_count():,} "
          f"mesh={dict(mesh.shape)} peers={n_peers} byz={sorted(byz)} "
          f"aggregator={agg_spec.canonical()} "
          f"scan={n_scan or '-'} "
          f"data={'device' if device_data else 'host'}")
    clock = ChunkClock(args.trace_dir)
    span = jax.profiler.TraceAnnotation
    final_loss = float("nan")
    if args.defense == "btard" and n_scan:
        v_prev = jax.tree.map(jnp.zeros_like, params)
        start_step = 0
        state_path = mem_path = ""
        if args.checkpoint_dir:
            os.makedirs(args.checkpoint_dir, exist_ok=True)
            state_path = os.path.join(args.checkpoint_dir, "state.msgpack")
            mem_path = os.path.join(args.checkpoint_dir,
                                    "membership.msgpack")
        if args.resume:
            example = {"params": params, "opt": opt_state, "v_prev": v_prev}
            state, start_step, ck_meta = load_checkpoint(state_path, example)
            params, opt_state, v_prev = (
                state["params"], state["opt"], state["v_prev"]
            )
            mem_tree, mem_step, _ = load_checkpoint(mem_path)
            if mem_step != start_step:
                raise RuntimeError(
                    f"checkpoint pair out of sync: state@{start_step} vs "
                    f"membership@{mem_step} — a crash mid-save; rerun "
                    "without --resume or restore the previous pair"
                )
            mem.restore_tree(mem_tree)
            weights = jnp.asarray(mem.weights())
            if start_step % n_scan:
                raise RuntimeError(
                    f"resume step {start_step} is not a multiple of "
                    f"--scan-steps {n_scan}; use the original chunking"
                )
            print(f"resumed at step {start_step} "
                  f"(banned={mem.banned_slots()}, arch={ck_meta.get('arch')})",
                  flush=True)
        rem = args.steps % n_scan
        rem_fn = None
        if rem:
            # a shorter trailing chunk needs its own fixed-length program
            rem_fn, _ = make_btard_scan_train_step(
                model, opt, mesh, shape, n_scan_steps=rem, tau=args.tau,
                clip_iters=args.clip_iters, attack=args.attack,
                use_pallas=args.use_pallas, aggregator=agg_spec,
                pipeline=pipe if device_data else None, extras=extras,
                **flat_cost,
            )
        for chunk in range(start_step, args.steps, n_scan):
            clock.begin()
            idxs = list(range(chunk, min(chunk + n_scan, args.steps)))
            # membership events fire at the chunk boundary: every join/leave
            # scheduled inside this chunk's window toggles its slot before
            # the dispatch (chunk-granular churn — the weights vector is
            # fixed for the compiled scan's duration)
            for s in idxs:
                mem.apply_events(s)
            weights = jnp.asarray(mem.weights())
            if len(idxs) < n_scan:
                step_fn = rem_fn
            steps_arr = jnp.asarray(idxs, jnp.int32)
            seeds = jnp.asarray([s * 7919 + 13 for s in idxs], jnp.int32)
            if device_data:
                with span("btard.host.dispatch"):
                    params, opt_state, metrics, verif, v_prev = step_fn(
                        params, opt_state, steps_arr, seeds, byz_mask,
                        weights, v_prev,
                    )
            else:
                batches = jax.tree.map(
                    lambda *ls: jnp.stack(ls),
                    *[pipe.batch(s, extras=extras) for s in idxs],
                )
                with span("btard.host.dispatch"):
                    params, opt_state, metrics, verif, v_prev = step_fn(
                        params, opt_state, batches, steps_arr, seeds,
                        byz_mask, weights, v_prev,
                    )
            # probation spot-checks: each scanned round reported every
            # peer's deviation from its public-seed recompute; feed the
            # probation slots' rows to the gate (ban on any mismatch,
            # promote after a clean window)
            with span("btard.host.fetch"):
                probes = np.asarray(verif["probe_mismatch"], np.float64)
            if probes.ndim == 1:
                probes = probes[None]
            for i, s in enumerate(idxs):
                mem.observe_probe(probes[i], s)
            # ban policy applied between dispatches from the LAST round's
            # checksums (mid-chunk rounds share the chunk's weights)
            bad = bf.checksum_offender_peers(verif["checksum"][-1])
            if not (args.attack != "none" or args.agg_attack):
                bad = []
            # audit-arm bans are unconditional: honest audits are exact
            # zeros, so a nonzero mismatch is a lie whatever the flags
            weights = apply_bans(weights, idxs[-1], bad,
                                 audit_offenders(verif))
            final_loss = float(metrics["loss"][-1])
            if chunk % max(args.log_every, 1) == 0:
                print(f"step {idxs[-1]:4d} loss={final_loss:.4f}"
                      f" checksum={float(metrics['checksum_max'][-1]):.2e}",
                      flush=True)
            if state_path:
                next_step = idxs[-1] + 1
                with span("btard.host.checkpoint"):
                    save_checkpoint(
                        state_path,
                        {"params": params, "opt": opt_state,
                         "v_prev": v_prev},
                        step=next_step,
                        meta={"arch": args.arch,
                              "aggregator": agg_spec.canonical()},
                    )
                    save_checkpoint(mem_path, mem.to_tree(), step=next_step)
                if args.halt_at is not None and next_step >= args.halt_at:
                    clock.close()
                    print(f"halt requested at step {args.halt_at}: "
                          f"checkpointed step {next_step}, exiting "
                          "(resume with --resume)", flush=True)
                    return _print_summary(json, mem, byz, final_loss,
                                          next_step)
            clock.end(len(idxs), params)
    else:
        for step in range(args.steps):
            clock.begin()
            mem.apply_events(step)
            weights = jnp.asarray(mem.weights())
            batch = pipe.batch(step, extras=extras)
            if args.defense == "btard":
                params, opt_state, metrics, verif = step_fn(
                    params, opt_state, batch, jnp.int32(step),
                    jnp.int32(step * 7919 + 13), byz_mask, weights,
                )
                extra = (f" checksum={float(metrics['checksum_max']):.2e}"
                         f" votes={float(metrics['votes_max']):.0f}")
                if isinstance(verif, dict) and "probe_mismatch" in verif:
                    mem.observe_probe(
                        np.asarray(verif["probe_mismatch"], np.float64), step
                    )
                # host-side ban policy: a violated partition checksum
                # implicates its aggregating peer (partition j <-> peer j)
                bad = bf.checksum_offender_peers(verif["checksum"])
                if not (args.attack != "none" or args.agg_attack):
                    bad = []
                weights = apply_bans(weights, step, bad,
                                     audit_offenders(verif))
            else:
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch, jnp.int32(step)
                )
                extra = ""
            final_loss = float(metrics["loss"])
            if step % args.log_every == 0:
                print(f"step {step:4d} loss={final_loss:.4f}{extra}",
                      flush=True)
            clock.end(1, params)
    clock.close()
    print(clock.done())
    summary = _print_summary(json, mem, byz, final_loss, args.steps)
    if args.checkpoint:
        save_checkpoint(args.checkpoint, {"params": params, "opt": opt_state},
                        step=args.steps, meta={"arch": args.arch})
        print("checkpoint saved:", args.checkpoint)
    return summary


class ChunkClock:
    """Wall time per chunk: the first chunk (which compiles) apart from the
    steady chunks after it. With ``trace_dir`` set, a profiler trace of the
    second and third chunk is written there."""

    def __init__(self, trace_dir=""):
        self.trace_dir = trace_dir
        self.tracing = False
        self.t0 = time.time()
        self.ends = []  # (wall time at the chunk's end, steps in it)

    def begin(self):
        if self.trace_dir and len(self.ends) == 1:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self.tracing = True

    def end(self, n_steps, state):
        self.ends.append((time.time(), n_steps))
        if self.tracing and len(self.ends) == 3:
            self.close(state)

    def close(self, state=None):
        if self.tracing:
            import jax

            jax.block_until_ready(state)
            jax.profiler.stop_trace()
            self.tracing = False

    def done(self) -> str:
        if not self.ends:
            return "done: 0 steps"
        (t1, n1), (t_last, _) = self.ends[0], self.ends[-1]
        steps = sum(n for _, n in self.ends)
        line = (f"done: {steps} steps in {t_last - self.t0:.1f}s; first "
                f"chunk ({n1} step{'s' * (n1 != 1)}, compile included) "
                f"{t1 - self.t0:.1f}s")
        if len(self.ends) > 1:
            line += (f"; then {(t_last - t1) / (steps - n1):.3f}s/step over "
                     f"{len(self.ends) - 1} chunks")
        return line


def _print_summary(json, mem, byz, final_loss, steps_done):
    """One machine-parseable line for CI assertions (churn gauntlet);
    returns the dict it prints."""
    s = mem.summary()
    s.update(byzantine=sorted(byz), final_loss=final_loss,
             steps_done=int(steps_done))
    print("SUMMARY " + json.dumps(s), flush=True)
    return s


if __name__ == "__main__":
    main()
