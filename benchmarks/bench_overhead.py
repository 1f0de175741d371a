"""Paper App. I.2: BTARD overhead vs plain All-Reduce.

Four views:
  * measured step time of the butterfly robust aggregation + verification
    tables vs a plain mean over stacked peer gradients, as d grows, for both
    the pure-jnp pipeline and the fused Pallas kernel (interpret mode on
    CPU — the interpreter is slow, so the *pass model* is the bandwidth
    signal there; on a TPU the kernels compile natively);
  * the HBM-pass model: the seed kernel family streamed the (n, d) peer
    stack 2*n_iters + 1 times per aggregation (norm phase + update phase per
    clip iteration, then a standalone table pass); the fused kernel's
    incremental-norm recurrence + verification epilogue does it in
    n_iters + 2 (see src/repro/kernels/DESIGN.md);
  * the communication model: per-peer bytes for AR vs BTARD
    (2d for ring/butterfly AR; BTARD adds O(n^2) scalars — independent of d,
    exactly the paper's §3.1 cost accounting), now PER AGGREGATOR SPEC:
    verifiable specs (the flagship and every verified:* wrapper) ride the
    butterfly at O(d) per peer plus size-independent table bytes, while the
    unwrapped baselines pay the trusted-PS O(n*d) all_gather; compressed:*
    specs carry per-codec ``bytes_on_wire`` / ``wire_reduction_x`` columns
    (int8 ~4x fewer all_to_all bytes; regression-gated);
  * the scan-engine view: steps/s of the legacy host protocol loop vs the
    jitted lax.scan ProtocolState engine (core.engine), at the default
    clip_iters=60 and at warm-start clip_iters=15 -> BENCH_scan.json;
  * the flat-cost scaling curve (n in {16, 64, 256, 1024}): per-peer table
    bytes + measured engine throughput/bans under sampled-digest audits and
    the hierarchical butterfly-of-butterflies (core.hierarchy), plus the
    per-phase SYMBOLIC comm model (sympy) cross-checked against the
    implementation — both gated in check_regression.py.

Emits BENCH_overhead.json + BENCH_scan.json next to this file (or --out-dir)
so the perf trajectory is machine-trackable across PRs; CI regenerates both
with --quick and gates merges on benchmarks/check_regression.py.
"""
import argparse
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timer
from repro.core.butterfly import (
    butterfly_clip,
    butterfly_clip_verified,
    get_random_directions,
    verification_tables,
)

_DIR = os.path.dirname(os.path.abspath(__file__))
JSON_PATH = os.path.join(_DIR, "BENCH_overhead.json")
SCAN_JSON_PATH = os.path.join(_DIR, "BENCH_scan.json")


def comm_model(n, d, bytes_per=4, payload_bytes=None, sidecar_bytes=0):
    """AR vs BTARD per-peer bytes, parameterized by the gradient payload
    dtype: ``payload_bytes`` is the bytes/coordinate on the butterfly
    all_to_all leg (defaults to ``bytes_per``, the f32 baseline; compressed
    specs ship 1-2), ``sidecar_bytes`` the codec sidecar traffic (one f32
    scale per payload each way). Returns (ar, btard_extra, bytes_on_wire)
    where bytes_on_wire is the all_to_all payload leg — the bytes a wire
    codec actually compresses."""
    pb = bytes_per if payload_bytes is None else payload_bytes
    ar = 2 * d * bytes_per  # reduce-scatter + all-gather per peer
    btard_extra = (2 * n * n + 3 * n) * bytes_per  # s-table, norms, hashes, mprng
    bytes_on_wire = d * pb + sidecar_bytes
    return ar, btard_extra, bytes_on_wire


def comm_model_per_spec(n, d, bytes_per=4):
    """Per-peer communication bytes per robust all-reduce, by registered
    AggregatorSpec (launch/steps.aggregation_stage topologies):

    * verifiable specs (butterfly_clip + every verified:* wrapper) run the
      butterfly — all_to_all its d/n-sized partition to every peer (~d
      sent) + the aggregated-partition all_gather (~d received) + the
      O(n^2)-scalar broadcast tables, independent of d;
    * compressed:* specs additionally quantize the all_to_all payload to
      their wire codec (int8: 1 byte/coordinate + one f32 scale sidecar
      per payload each way; bf16: 2 bytes) — ``bytes_on_wire`` is that
      compressed leg and ``wire_reduction_x`` its reduction vs the f32
      butterfly payload (the regression-gated codec claim); the aggregate
      all_gather rides the transport dtype, codec-independent;
    * non-verifiable specs all_gather the FULL peer stack (the trusted-PS
      model): n*d received per peer, zero tables.

    This is the paper's §3.1 cost accounting extended across the spec
    registry: wrapping a baseline into its verified: form REPLACES the
    O(n*d) PS gather with the O(d)-per-peer butterfly plus size-independent
    table traffic — verification makes the communication model BETTER, not
    worse, for n > 2 — and the compressed: wrapper then shrinks the
    dominant butterfly leg by ~4x (int8) on top.
    """
    from repro.core import compression as comp
    from repro.core.aggregators import REGISTRY, AggregatorSpec

    out = {}

    def cell(defn, payload_bytes, sidecar):
        if defn.verifiable:
            table = (2 * n * n + 3 * n) * bytes_per
            _, _, wire = comm_model(
                n, d, bytes_per, payload_bytes, sidecar
            )
            # + the aggregated-partition all_gather (transport dtype)
            per_peer = wire + d * bytes_per + table
            topology = "butterfly"
        else:
            table = 0
            wire = (n + 1) * d * bytes_per  # send d, gather the n*d stack
            per_peer = wire
            topology = "ps_all_gather"
        return {
            "topology": topology,
            "payload_bytes_per_coord": payload_bytes,
            "sidecar_bytes": sidecar,
            "bytes_on_wire": wire,
            "per_peer_bytes": per_peer,
            "table_bytes": table,
            "per_peer_over_ar": per_peer / (2 * d * bytes_per),
            # the codec claim: f32 all_to_all leg / this spec's leg
            "wire_reduction_x": (d * bytes_per) / wire
            if topology == "butterfly" else 1.0,
        }

    for name, defn in sorted(REGISTRY.items()):
        if name.startswith(comp.PREFIX):
            codec = comp.codec_of(AggregatorSpec(name))  # declared default
            out[name] = cell(
                defn, comp.CODEC_BYTES[codec], 2 * n * bytes_per
            )
            # the non-default codec variant, same spec machinery
            for alt in comp.CODECS:
                if alt != codec:
                    out[f"{name}:codec={alt}"] = cell(
                        defn, comp.CODEC_BYTES[alt], 2 * n * bytes_per
                    )
        else:
            out[name] = cell(defn, bytes_per, 0)
    return out


def symbolic_comm_model(bytes_per=4):
    """Per-phase SYMBOLIC communication-complexity model (sympy) of one
    robust all-reduce round, per verification mode — the closed forms the
    numeric models above instantiate, kept as expressions so the asymptotic
    claims (table bytes O(n^2) -> O(n*k) -> O(n^2/g + g^2)) are
    machine-checkable rather than prose.

    Symbols: n peers, d gradient dim, g groups, k sampled digest columns
    per step (k = m_validators * audit_k), b bytes/scalar. Phases follow
    launch/steps.aggregation_stage: the gradient all_to_all (~d sent per
    peer), the aggregate all_gather (~d received), and the verification
    table broadcast (digest + norm columns + the 3n checksum/vote/hash
    sidecars; hierarchical mode adds the g x g level-2 digest exchange).

    Every expression is cross-checked numerically against
    repro.core.hierarchy.table_scalars at the evaluation points — the gate
    in check_regression.py fails if the symbolic and implemented models
    ever drift apart. Returns a JSON-ready dict (expressions as strings).
    """
    import sympy as sp

    from repro.core import hierarchy as hier

    n, d, g, k, b = sp.symbols("n d g k b", positive=True)
    gs = n / g

    class Communication:
        """Accumulates per-phase symbolic costs (pia-mpc complexity idiom):
        one expression per protocol phase, summed into the per-peer round
        total."""

        def __init__(self):
            self.phases = {}

        def add(self, phase, expr):
            self.phases[phase] = sp.expand(self.phases.get(phase, 0) + expr)

        def total(self):
            return sp.expand(sum(self.phases.values(), sp.Integer(0)))

        def table_total(self):
            return sp.expand(sum(
                (e for p, e in self.phases.items() if "table" in p
                 or "digest" in p), sp.Integer(0)))

        def as_dict(self):
            return {p: str(e) for p, e in self.phases.items()}

    def build(mode):
        c = Communication()
        c.add("gradient_all_to_all", d * b)  # each peer ships d coords total
        c.add("aggregate_all_gather", d * b)
        if mode == "full":
            c.add("table_broadcast", (2 * n**2 + 3 * n) * b)
        elif mode == "sampled":
            # only the k sampled digest columns broadcast; checksum/vote/
            # hash sidecars stay per-column-owner (3n)
            c.add("table_broadcast", (2 * n * k + 3 * n) * b)
        elif mode == "hierarchical":
            c.add("table_broadcast", (2 * gs**2 + 3 * gs) * b)
            c.add("level2_digest_exchange", (2 * g**2 + 3 * g) * b)
        elif mode == "hierarchical_sampled":
            # k <= gs columns sampled within each group
            c.add("table_broadcast", (2 * gs * k + 3 * gs) * b)
            c.add("level2_digest_exchange", (2 * g**2 + 3 * g) * b)
        return c

    modes = {m: build(m) for m in (
        "full", "sampled", "hierarchical", "hierarchical_sampled")}
    full_tables = modes["full"].table_total()

    # numeric cross-check vs the implemented model (core.hierarchy):
    # sympy expression == table_scalars() at every evaluation point, exactly
    points = [
        {"n": 64, "g": 8, "k": 2},
        {"n": 256, "g": 16, "k": 4},
        {"n": 1024, "g": 32, "k": 4},
    ]
    checks = []
    for pt in points:
        subs = {n: pt["n"], g: pt["g"], k: pt["k"], b: 1}
        impl = {
            "full": hier.table_scalars(pt["n"]),
            "sampled": hier.table_scalars(
                pt["n"], m_validators=1, audit_k=pt["k"]),
            "hierarchical": hier.table_scalars(pt["n"], groups=pt["g"]),
            "hierarchical_sampled": hier.table_scalars(
                pt["n"], m_validators=1, audit_k=pt["k"], groups=pt["g"]),
        }
        sym = {m: int(c.table_total().subs(subs)) for m, c in modes.items()}
        checks.append({
            "point": pt,
            "symbolic": sym,
            "implemented": impl,
            "match": sym == impl,
        })

    return {
        "symbols": {"n": "peers", "d": "gradient dim", "g": "groups",
                    "k": "sampled digest columns/step (m_validators*audit_k)",
                    "b": "bytes/scalar"},
        "phases": {m: c.as_dict() for m, c in modes.items()},
        "per_peer_total": {m: str(c.total()) for m, c in modes.items()},
        "table_bytes": {m: str(c.table_total()) for m, c in modes.items()},
        "table_ratio_vs_full": {
            m: str(sp.simplify(c.table_total() / full_tables))
            for m, c in modes.items()
        },
        "cross_check": checks,
        "bytes_per": bytes_per,
    }


def _detect_bound(n, m_val, groups, audit_k=None):
    """Steps until the sign_flip workload's Byzantine peers are provably
    banned. Hierarchical full-table mode trips the GROUP-majority
    Delta_max vote within a step or two — a lone sign-flipper shifts its
    gs-peer group mean far past delta_max for every member, and the vote
    + exoneration recompute bans exactly the cheater. Under sampled
    digests the vote only sees SAMPLED columns (the zero-scatter
    invariant zeroes unsampled norms on both sides), so the composed
    mode's time-to-ban is the age-priority column draw reaching the
    cheater's own column — the staleness window ceil(n/(m*k)) + 2 — or
    the validator peer-audit backstop, whichever is sooner. Flat modes at
    larger n dilute the corruption across the global mean (V3 stays
    silent), so time-to-ban is that audit backstop alone: age-priority
    CHOOSETARGET covers every peer within ~ceil(n/m) steps. The +slack
    absorbs validator rotation (a peer serving as validator is not
    auditable that step)."""
    audit_cover = math.ceil(n / m_val)
    if groups:
        if audit_k is None:
            return 12
        staleness = math.ceil(n / (m_val * audit_k)) + 2
        return min(staleness, audit_cover) + 10
    return audit_cover + 10


def flat_cost_scaling(fast=True):
    """The tentpole scaling curve: per-peer verification-table bytes
    (analytic — core.hierarchy.table_scalars) and measured scan-engine
    throughput + ban behaviour as n grows, for the four mode combinations
    {full, sampled, hierarchical, hierarchical+sampled}.

    The analytic rows cover every n; the measured rows run the full
    ProtocolState engine (sign_flip Byzantine workload, Delta_max votes +
    validator audits live) on the n's a CI runner can afford — quick mode
    stops at 64, full mode at 1024. Each cell runs for its mode's
    :func:`_detect_bound` steps (capped), so the ban outcome is a
    guarantee check, not a race: cells whose bound fits under the cap
    carry ``bans_gated=True`` and check_regression.py requires
    ``bans_exact`` there; over-cap cells (flat modes at n=1024 — the
    audit backstop needs ~n/m steps — and the composed mode at n=1024,
    whose column-staleness window is ~n/(m*k)) are throughput-only,
    gated on zero honest bans. Also gated: at n=1024 the hierarchical+sampled per-peer
    table bytes must be <= 10% of full.
    """
    from repro.core import hierarchy as hier
    from repro.core.engine import EngineConfig, init_state, make_scan_runner

    M_VAL, AUDIT_K = 2, 2
    step_cap = 64 if fast else 160
    ns = [16, 64, 256, 1024]
    measured_ns = [16, 64] if fast else [16, 64, 256, 1024]
    rows = []
    for n in ns:
        g = int(np.sqrt(n))
        modes = {
            "full": {},
            "sampled": {"audit_k": AUDIT_K},
            "hierarchical": {"groups": g},
            "hierarchical_sampled": {"audit_k": AUDIT_K, "groups": g},
        }
        table_bytes = {
            m: hier.table_bytes(
                n, m_validators=M_VAL, audit_k=kw.get("audit_k"),
                groups=kw.get("groups"),
            )
            for m, kw in modes.items()
        }
        row = {
            "n": n,
            "groups": g,
            "audit_k": AUDIT_K,
            "m_validators": M_VAL,
            "table_bytes": table_bytes,
            "table_frac_vs_full": {
                m: tb / table_bytes["full"] for m, tb in table_bytes.items()
            },
        }
        if n in measured_ns:
            d = 4 * n
            # one Byzantine per far-apart group so no group is majority-Byz
            byz_ids = (0, n // 2)
            byz = jnp.zeros((n,)).at[jnp.asarray(byz_ids)].set(1.0)
            measured = {}
            for m, kw in modes.items():
                bound = _detect_bound(
                    n, M_VAL, kw.get("groups"), kw.get("audit_k")
                )
                gated = bound <= step_cap
                # over-cap cells (flat audit coverage ~n/m steps at
                # n=1024) are throughput-only: short program, bans
                # reported but not gated
                steps = bound if gated else 12
                cfg = EngineConfig(
                    n=n, d=d, attack="sign_flip", lam=100.0, start_step=0,
                    clip_iters=5, m_validators=M_VAL, delta_max=25.0,
                    aggregator="verified:mean", **kw,
                )
                runner = make_scan_runner(
                    cfg, _scaling_grads_fn(n, d), steps
                )
                st0 = init_state(cfg, seed=0)
                params = jnp.zeros(())
                state, _, outs = runner(st0, byz, params)  # warmup+trace
                jax.block_until_ready(state)
                reps = 1 if steps >= 48 else 2
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    state, _, outs = runner(st0, byz, params)
                    jax.block_until_ready(state)
                    best = min(best, time.perf_counter() - t0)
                banned = sorted(
                    int(i)
                    for i in np.nonzero(np.asarray(state.ban_step) >= 0)[0]
                )
                measured[m] = {
                    "steps": steps,
                    "detect_bound": bound,
                    "bans_gated": gated,
                    "steps_per_s": steps / best,
                    "banned": banned,
                    "byzantine": list(byz_ids),
                    "bans_exact": banned == sorted(byz_ids),
                    "honest_banned": sorted(
                        set(banned) - set(int(i) for i in byz_ids)
                    ),
                }
                emit(
                    f"overhead/scaling/n={n}/{m}",
                    1e6 * best / steps,
                    f"sps={steps / best:.1f};"
                    f"table_bytes={table_bytes[m]};"
                    f"frac={row['table_frac_vs_full'][m]:.4f};"
                    f"steps={steps};gated={gated};"
                    f"bans_exact={measured[m]['bans_exact']}",
                )
            row["measured"] = measured
        rows.append(row)
    return {"step_cap": step_cap, "rows": rows}


def _scaling_grads_fn(n, d):
    """Honest per-step gradients for the scaling bench: unit-variance
    noise around a fixed descent direction; the engine's phase_attack
    applies the configured Byzantine corruption itself."""
    mu = jax.random.normal(jax.random.key(7), (d,)) * 0.1

    def grads_fn(params, t, flips):
        key = jax.random.fold_in(jax.random.key(1), t)
        G = mu[None] + jax.random.normal(key, (n, d), jnp.float32)
        return G, G

    return grads_fn


def hbm_pass_model(n_iters, n, d, bytes_per=4, adaptive_iters=2):
    """HBM traffic of the full aggregation workload per robust all-reduce:
    across all n partitions the streamed stack totals n * d values (each
    partition is an (n, d/n) peer stack).

    seed two-phase kernel + standalone table kernel: 2*n_iters + 1 passes;
    fused incremental-norm kernel with verification epilogue: n_iters + 2;
    adaptive early-exit driver: iters_run + 2 (jnp prologue + one pass per
    iteration actually run + the single verification epilogue) —
    ``adaptive_iters`` is the warm-start steady-state iteration count
    (measured 1-2 on the convergence workloads, vs the fixed 60 budget).
    """
    stack = n * d * bytes_per
    return {
        "seed_passes": 2 * n_iters + 1,
        "fused_passes": n_iters + 2,
        "adaptive_passes": adaptive_iters + 2,
        "seed_bytes": (2 * n_iters + 1) * stack,
        "fused_bytes": (n_iters + 2) * stack,
        "adaptive_bytes": (adaptive_iters + 2) * stack,
        "pass_speedup": (2 * n_iters + 1) / (n_iters + 2),
        "adaptive_pass_speedup": (n_iters + 2) / (adaptive_iters + 2),
    }


# (d_model, vocab_size) ladder for the real-model scaling curve: reduced
# ALBERT scaled along width AND vocab so params grow ~geometrically. Quick
# mode runs the first three (CI-affordable on CPU); full mode appends the
# d512/30k-vocab point (~39M params, the committed-baseline ceiling).
MODEL_SCALING_SIZES = ((128, 2048), (192, 4096), (256, 8192))
MODEL_SCALING_SIZES_FULL = MODEL_SCALING_SIZES + ((512, 30000),)
MODEL_SCALING_AGG = "compressed:verified:mean:codec=bf16"


def model_scaling_bench(fast=True, steps=4, n_peers=4, seq_len=16, batch=2):
    """Real-model gauntlet scaling curve: model size (flat gradient dim d)
    vs measured scanned-BTARD steps/s, per-peer wire bytes, and table
    overhead fraction, under the bf16 wire codec with full verification and
    one sign-flip Byzantine peer. The byte columns are analytic
    (:func:`comm_model` — same accounting as comm_per_spec); the ban
    columns are protocol guarantees (the attacker must be banned, no honest
    peer ever accused); steps/s is the one wall-clock column.

    The paper's flat-cost claim, restated on real models: table bytes are
    size-INDEPENDENT, so table overhead fraction must fall as the model
    grows while the wire bytes track d exactly.
    """
    import dataclasses

    from repro.configs import get_config, reduce_config
    from repro.core import AttackConfig, BTARDTrainer, TrainerConfig
    from repro.core.compression import CODEC_BYTES
    from repro.data import TokenPipeline
    from repro.models.model import Model
    from repro.optim import sgd

    cfg0 = reduce_config(get_config("albert-large"))
    sizes = MODEL_SCALING_SIZES if fast else MODEL_SCALING_SIZES_FULL
    byz = (n_peers - 1,)
    rows = []
    for dm, vocab in sizes:
        cfg = dataclasses.replace(
            cfg0, name=f"albert-d{dm}-v{vocab}", d_model=dm, d_ff=4 * dm,
            n_heads=max(2, dm // 64), n_kv_heads=max(2, dm // 64),
            head_dim=64, vocab_size=vocab,
        )
        m = Model(cfg)
        pipe = TokenPipeline(vocab, seq_len, batch)
        tr = BTARDTrainer(
            lambda p, b, m=m: m.loss_fn(p, b)[0],
            m.init_params(jax.random.key(0)),
            lambda peer, step, flipped, pipe=pipe: pipe.device_batch(step, peer),
            TrainerConfig(
                n_peers=n_peers, byzantine=byz,
                attack=AttackConfig(kind="sign_flip", start_step=0),
                defense="btard", aggregator=MODEL_SCALING_AGG,
                tau=2.0, clip_iters=5, m_validators=1,
            ),
            optimizer=sgd(0.05),
        )
        d = tr.d
        tr.run_scan(steps)  # warmup: trace + compile (bans land here)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            tr.run_scan(steps)
            best = min(best, time.perf_counter() - t0)
        pb = CODEC_BYTES["bf16"]
        _, table, wire = comm_model(
            n_peers, d, 4, payload_bytes=pb, sidecar_bytes=2 * n_peers * 4
        )
        per_peer = wire + d * 4 + table  # + aggregate all_gather (transport)
        row = {
            "name": cfg.name,
            "params": d,
            "d_model": dm,
            "vocab": vocab,
            "steps_per_s": steps / best,
            "payload_bytes_per_coord": pb,
            "wire_bytes_per_peer": wire,
            "per_peer_bytes": per_peer,
            "table_bytes": table,
            "table_overhead_frac": table / per_peer,
            "byzantine": sorted(byz),
            "banned": sorted(tr.banned),
            "honest_banned": sorted(set(tr.banned) - set(byz)),
        }
        rows.append(row)
        emit(
            f"overhead/model_scaling/{cfg.name}",
            1e6 * best / steps,
            f"params={d};sps={row['steps_per_s']:.2f};"
            f"wire={wire};table_frac={row['table_overhead_frac']:.2e};"
            f"banned={row['banned']}",
        )
    return {
        "arch": "albert-large (reduced, scaled)",
        "aggregator": MODEL_SCALING_AGG,
        "n_peers": n_peers,
        "seq_len": seq_len,
        "batch": batch,
        "steps": steps,
        "rows": rows,
    }


def scan_engine_bench(steps=None, fast=True, out_dir=None):
    """Legacy host loop vs jitted lax.scan ProtocolState engine: steps/s on
    the controlled classification workload (16 peers, 7 Byzantine,
    sign-flip), at clip_iters=60 (the protocol default), at the warm-start
    budget clip_iters=15, and with the adaptive early-exit budget
    (``adaptive_tol``, cap 60) — plus adaptive-vs-fixed CURVES so the
    budget/steps-per-second trade-off is machine-trackable. Writes
    BENCH_scan.json."""
    from benchmarks.common import classification_setup
    from repro.core import AttackConfig, BTARDTrainer, TrainerConfig
    from repro.optim import sgd

    if steps is None:
        # 30-step sections put the jit-dispatch overhead at ~30% of the
        # measurement and compress the adaptive-vs-fixed ratio; 60 keeps
        # quick mode quick while the ratio tracks the full-mode value
        steps = 60 if fast else 100
    scan_json = os.path.join(out_dir or _DIR, "BENCH_scan.json")
    # dim=512 -> d ≈ 2k: CenteredClip is a real fraction of the step, so
    # the adaptive-vs-fixed ratio measures the clip budget rather than
    # per-step dispatch jitter (at the tests' dim=16 the clip is ~nothing
    # and the ratio is noise-bound)
    loss_fn, params0, batch_fn, accuracy = classification_setup(dim=512)

    def make(clip_iters, warm_start=False, adaptive_tol=None,
             defense="btard"):
        cfg = TrainerConfig(
            n_peers=16,
            byzantine=tuple(range(9, 16)),
            attack=AttackConfig(kind="sign_flip", start_step=5),
            defense=defense,
            tau=1.0,
            clip_iters=clip_iters,
            m_validators=2,
            seed=0,
            warm_start=warm_start,
            adaptive_tol=adaptive_tol,
        )
        return BTARDTrainer(
            loss_fn, params0, batch_fn, cfg, optimizer=sgd(0.3, momentum=0.9)
        )

    def time_run(method, clip_iters, warm_start=False, adaptive_tol=None,
                 reps=None):
        tr = make(clip_iters, warm_start, adaptive_tol)
        fn = getattr(tr, method)
        fn(steps)  # warmup: traces + compiles everything
        if reps is None:
            # a 30-step scan section is ~10 ms — single-shot timing is
            # dispatch-jitter noise, so take best-of-many for the fast
            # methods (the legacy host loop is 50x slower; 2 reps suffice)
            reps = 2 if method == "run" else 8
        best = float("inf")
        for _ in range(reps):  # best-of-reps: steady state (bans settled —
            t0 = time.perf_counter()  # the regime a long run lives in)
            fn(steps)
            best = min(best, time.perf_counter() - t0)
        iters = [
            h["clip_iters_used"]
            for h in tr.history[steps:]
            if "clip_iters_used" in h
        ]
        cell = {
            "steps_per_s": steps / best,
            "clip_iters": clip_iters,
            "acc": accuracy(tr.unraveled_params()),
            "banned": len(tr.banned),
        }
        if warm_start:
            cell["warm_start"] = True
        if adaptive_tol is not None:
            cell["adaptive_tol"] = adaptive_tol
            cell["clip_iters_used_mean"] = float(np.mean(iters)) if iters else None
        return cell

    loop = time_run("run", 60, reps=1)
    scan = time_run("run_scan", 60)
    warm = time_run("run_scan", 15, warm_start=True)
    # the device-resident default: adaptive early exit at the protocol-default
    # cap (60) with warm start — the acceptance headline vs the fixed scan
    adaptive = time_run("run_scan", 60, warm_start=True, adaptive_tol=1e-4)

    # headline ratio from INTERLEAVED paired timing: the two cells alternate
    # within one loop, so a machine-wide slowdown (CI runners!) hits both
    # symmetrically and best-of picks each cell's cleanest samples — the
    # independently-timed cells above keep the absolute steps/s numbers
    tr_fixed = make(60)
    tr_adapt = make(60, warm_start=True, adaptive_tol=1e-4)
    tr_fixed.run_scan(steps)
    tr_adapt.run_scan(steps)
    best_fixed = best_adapt = float("inf")
    for _ in range(8):
        t0 = time.perf_counter()
        tr_fixed.run_scan(steps)
        best_fixed = min(best_fixed, time.perf_counter() - t0)
        t0 = time.perf_counter()
        tr_adapt.run_scan(steps)
        best_adapt = min(best_adapt, time.perf_counter() - t0)
    adaptive_vs_scan = best_fixed / max(best_adapt, 1e-9)

    # --- the AggregatorSpec comparison axis: every registered aggregator
    # through the SAME scanned engine on the same attacked workload. The
    # block existing at all proves each spec is jit/scan-clean; the
    # flagship's advantage over the fixed scan stays gated separately
    # (adaptive_speedup_vs_scan_x >= 1.15 in check_regression.py).
    from repro.core.aggregators import REGISTRY, registered_aggregators

    agg_steps = max(steps // 2, 20)
    aggregator_comparison = {}
    for name in registered_aggregators():
        defense = "btard" if name == "butterfly_clip" else name
        tr = make(60, warm_start=name == "butterfly_clip",
                  adaptive_tol=1e-4 if name == "butterfly_clip" else None,
                  defense=defense)
        tr.run_scan(agg_steps)  # warmup: trace + compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            tr.run_scan(agg_steps)
            best = min(best, time.perf_counter() - t0)
        aggregator_comparison[name] = {
            "steps_per_s": agg_steps / best,
            "acc": accuracy(tr.unraveled_params()),
            "banned": len(tr.banned),
            "verifiable": REGISTRY[name].verifiable,
        }
        emit(
            f"overhead/aggregator/{name}",
            1e6 * best / agg_steps,
            f"sps={agg_steps / best:.1f};"
            f"acc={aggregator_comparison[name]['acc']:.3f};"
            f"banned={aggregator_comparison[name]['banned']}",
        )

    fixed_curve = [scan, warm] + [time_run("run_scan", 30)]
    adaptive_curve = [
        time_run("run_scan", 60, warm_start=True, adaptive_tol=tol)
        for tol in (1e-2, 1e-6)
    ] + [adaptive]
    payload = {
        "bench": "scan_engine",
        "backend": jax.default_backend(),
        "steps": steps,
        "n_peers": 16,
        "legacy_loop": loop,
        "scan_engine": scan,
        "scan_engine_warm15": warm,
        "scan_engine_adaptive": adaptive,
        "aggregator_comparison": aggregator_comparison,
        # real-model gauntlet: scanned BTARD over scaled zoo LMs
        "model_scaling": model_scaling_bench(fast=fast),
        "fixed_curve": fixed_curve,
        "adaptive_curve": adaptive_curve,
        "scan_speedup_x": scan["steps_per_s"] / max(loop["steps_per_s"], 1e-9),
        "warm_speedup_x": warm["steps_per_s"] / max(loop["steps_per_s"], 1e-9),
        "adaptive_speedup_x": adaptive["steps_per_s"]
        / max(loop["steps_per_s"], 1e-9),
        # the acceptance ratio: adaptive early exit vs the PR 2 fixed-budget
        # scan path, both at protocol-default settings (cap/budget 60),
        # measured pairwise-interleaved (above)
        "adaptive_speedup_vs_scan_x": adaptive_vs_scan,
    }
    with open(scan_json, "w") as f:
        json.dump(payload, f, indent=2)
    emit(
        "overhead/scan_engine",
        1e6 / max(scan["steps_per_s"], 1e-9),
        f"loop_sps={loop['steps_per_s']:.1f};scan_sps={scan['steps_per_s']:.1f};"
        f"warm15_sps={warm['steps_per_s']:.1f};"
        f"adaptive_sps={adaptive['steps_per_s']:.1f};"
        f"speedup={payload['scan_speedup_x']:.1f}x;"
        f"adaptive_vs_scan={payload['adaptive_speedup_vs_scan_x']:.2f}x;"
        f"acc_loop={loop['acc']:.3f};acc_scan={scan['acc']:.3f};"
        f"acc_adaptive={adaptive['acc']:.3f};"
        f"iters_used={adaptive['clip_iters_used_mean']}",
    )
    print(f"wrote {scan_json}", flush=True)
    return payload


def main(fast=True, out_dir=None):
    if fast and out_dir is None:
        # quick mode must never clobber the committed (CI-gated, full-mode)
        # baselines: park its JSON in a scratch subdir unless the caller
        # explicitly chose a destination
        out_dir = os.path.join(_DIR, "quick")
        os.makedirs(out_dir, exist_ok=True)
        print(f"quick mode: writing BENCH_*.json to {out_dir} "
              "(committed baselines are full-mode; pass --out-dir to "
              "override)", flush=True)
    json_path = os.path.join(out_dir or _DIR, "BENCH_overhead.json")
    n, n_iters = 16, 20
    dims = [1 << 14, 1 << 17] if fast else [1 << 14, 1 << 17, 1 << 20, 1 << 23]
    # interpret-mode pallas is CPU-interpreter-bound; keep its sizes sane
    fused_dims = [d for d in dims if d <= 1 << 17]
    records = []
    for d in dims:
        g = jax.random.normal(jax.random.key(0), (n, d))
        z = get_random_directions(7, n, -(-d // n))

        mean_fn = jax.jit(lambda x: x.mean(0))
        us_mean = timer(mean_fn, g, reps=10)

        def full_btard(x):
            agg, parts = butterfly_clip(x, tau=1.0, n_iters=n_iters)
            s, norms = verification_tables(parts, agg, z, 1.0)
            return agg, s, norms

        us_btard = timer(jax.jit(full_btard), g, reps=5)

        us_fused = None
        if d in fused_dims:
            def fused_btard(x):
                agg, _parts, s, norms = butterfly_clip_verified(
                    x, 1.0, z, n_iters=n_iters, use_pallas=True
                )
                return agg, s, norms

            us_fused = timer(jax.jit(fused_btard), g, reps=3)

        ar, extra, _ = comm_model(n, d)
        passes = hbm_pass_model(n_iters, n, d)
        emit(
            f"overhead/d={d}",
            us_btard,
            f"mean_us={us_mean:.1f};overhead_x={us_btard/max(us_mean,1e-9):.2f};"
            f"fused_us={-1.0 if us_fused is None else us_fused:.1f};"
            f"passes_seed={passes['seed_passes']};passes_fused={passes['fused_passes']};"
            f"pass_speedup={passes['pass_speedup']:.2f};"
            f"comm_ar_bytes={ar};comm_btard_extra_bytes={extra};"
            f"extra_frac={extra/ar:.4f}",
        )
        records.append(
            {
                "d": d,
                "n_peers": n,
                "n_iters": n_iters,
                "mean_us": us_mean,
                "btard_jnp_us": us_btard,
                "btard_fused_interpret_us": us_fused,
                "overhead_x": us_btard / max(us_mean, 1e-9),
                "hbm_pass_model": passes,
                "comm_ar_bytes": ar,
                "comm_btard_extra_bytes": extra,
            }
        )
    # the tentpole scaling curve + the symbolic per-phase comm model: table
    # bytes flat in n under sampling/hierarchy, cross-checked sympy-vs-
    # implementation, with measured engine cells where CI can afford them
    scaling = flat_cost_scaling(fast=fast)
    symbolic = symbolic_comm_model()
    for chk in symbolic["cross_check"]:
        if not chk["match"]:
            emit("overhead/symbolic_mismatch", 1.0, str(chk))
    # per-aggregator communication model at the largest measured dim: the
    # verified: wrapper's butterfly O(d) per peer vs the PS O(n*d) gather
    comm_per_spec = comm_model_per_spec(n, dims[-1])
    for spec_name, cell in comm_per_spec.items():
        emit(
            f"overhead/comm/{spec_name}",
            cell["per_peer_bytes"] / 1e3,
            f"topology={cell['topology']};table_bytes={cell['table_bytes']};"
            f"per_peer_over_ar={cell['per_peer_over_ar']:.2f};"
            f"bytes_on_wire={cell['bytes_on_wire']};"
            f"wire_reduction={cell['wire_reduction_x']:.2f}x",
        )
    payload = {
        "bench": "overhead",
        "backend": jax.default_backend(),
        "pallas_mode": "compiled" if jax.default_backend() == "tpu"
        else "interpret",
        "comm_per_spec": {"n_peers": n, "d": dims[-1], "specs": comm_per_spec},
        "flat_cost_scaling": scaling,
        "symbolic_comm": symbolic,
        "records": records,
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {json_path}", flush=True)
    scan_engine_bench(fast=fast, out_dir=out_dir)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: small dims, 60-step scan cells, output "
                         "parked in benchmarks/quick/ unless --out-dir")
    ap.add_argument("--out-dir", default=None,
                    help="write BENCH_*.json here instead of benchmarks/ "
                         "(CI writes to a scratch dir and diffs against the "
                         "committed baselines via check_regression.py)")
    args = ap.parse_args()
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)
    main(fast=args.quick, out_dir=args.out_dir)
