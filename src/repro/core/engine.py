"""Jit/scan-compatible BTARD protocol engine (paper Alg. 1-7).

The legacy ``core.protocol.BTARDProtocol`` simulated every phase host-side:
numpy loops, sha256 commitments, python accusation lists — one device
round-trip per phase, so the *protocol* dominated step time beyond toy
sizes. This module is the same state machine as pure functions over an
explicit :class:`ProtocolState` pytree, so one full step jit-compiles and N
steps run under ``lax.scan`` with zero host synchronisation:

    compute_grads -> apply_attack -> aggregate (AggregatorSpec) -> verify
    -> accuse/ban

The aggregation phase is spec-dispatched (``EngineConfig.aggregator``,
``core.aggregators``): verifiable specs — the ButterflyClip flagship and
the ``verified:<base>`` wrappers over the coordinatewise baselines
(``core.verification``: generalized contribution digests in place of the
CenteredClip-residual tables) — run the full verification pipeline;
non-verifiable baseline specs (krum, geometric_median, trusted-PS
centered_clip and the unwrapped coordinatewise fns) run the same step with
verify/accuse/ban degraded to no-ops — the paper's Fig. 3 comparison axis
inside one engine.

Equivalences to the wire protocol (all recorded in kernels/DESIGN.md):

* sha256 commitments ≡ array equality — a commitment catches exactly a
  value that differs from the recomputed one, so the engine compares
  arrays directly (bit-identical rows never trip, attacked rows always do);
* MPRNG commit/reveal ≡ a deterministic per-step fold of the run's base
  key — unbiasable by construction, like the host protocol's abort-ban
  rule (the abort-bias attack is modelled by its *outcome*: aborters get
  banned);
* the banned-peer set shrink ≡ a static-shape ``active`` mask: banned rows
  are zeroed and carry weight 0, partition ownership stays peer j <->
  partition j (the butterfly assignment of Alg. 2).

``core.protocol.BTARDProtocol`` is now a thin host wrapper over
:func:`protocol_step` that mirrors bans/accusations out of the state pytree
(host ``grad_fn`` support + the legacy ``StepInfo`` API), so a scanned
N-step run and N wrapper calls are the *same computation* — property-tested
in ``tests/test_engine.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg_mod
from repro.core import attacks as attacks_mod
from repro.core import butterfly as bf
from repro.core import compression as comp_mod
from repro.core import hierarchy as hier_mod
from repro.core import sybil as sybil_mod
from repro.core import verification as verif_mod
from repro.core.sybil import (  # noqa: F401 — re-exported lifecycle codes
    SLOT_ACTIVE,
    SLOT_BANNED,
    SLOT_PROBATION,
    SLOT_VACANT,
)

# Ban reason codes (StepOutputs.ban_reason_now / ProtocolState.ban_reason)
BAN_NONE = 0
BAN_CHEATER = 1  # accused and the recompute proved it (ACCUSE, Alg. 4)
BAN_COVERUP = 2  # misreported s for a banned peer's partition (Alg. 4 L11-13)
BAN_FALSE_ACCUSER = 3  # slandered an honest peer (Hammurabi rule, Alg. 3)
BAN_MPRNG = 4  # aborted / mismatched the MPRNG commit-reveal (App. A.2)
BAN_SYBIL = 5  # failed a probation spot-check (Sybil gate, §3.3 / App. F)

BAN_REASON_NAMES = {
    BAN_NONE: "",
    BAN_CHEATER: "accusation verified (ACCUSE)",
    BAN_COVERUP: "covered up a banned peer (s mismatch)",
    BAN_FALSE_ACCUSER: "false accusation",
    BAN_MPRNG: "mprng abort/mismatch",
    BAN_SYBIL: "probation spot-check failed (sybil gate)",
}

# Membership event codes (ProtocolState.events rows: [step, kind, slot, id])
EVENT_NONE = 0
EVENT_JOIN = 1
EVENT_LEAVE = 2


class ProtocolState(NamedTuple):
    """One BTARD run's full per-step carry — a plain pytree of arrays.

    ``key`` is the run's base PRNG key; every draw is a fold of (key, step,
    phase), so a step's randomness is a pure function of the state — the
    property that makes scan and per-step execution bit-identical.

    The peer axis is a static ``n``-slot CAPACITY, not a fixed peer set:
    ``lifecycle`` tracks each slot through vacant → probation → active →
    banned (``core.sybil``), ``events`` is the statically-shaped join/leave
    schedule threaded through the scan (same idiom as the delay ring
    buffer), and the ``id_*`` ledgers are keyed by IDENTITY — they outlive
    the slot's occupant, so churn can never launder a ban or an accusation
    history (``slot_identity`` maps slot → current occupant, -1 vacant).
    """

    step: jnp.ndarray  # () i32 — t
    key: jnp.ndarray  # PRNG key (base of the per-step chain)
    active: jnp.ndarray  # (n,) f32 — 1 active (== lifecycle SLOT_ACTIVE)
    validator: jnp.ndarray  # (n,) f32 — C_t (elected at end of step t-1)
    prev_agg: jnp.ndarray  # (n_parts, part) f32 — last aggregate (warm start)
    ban_step: jnp.ndarray  # (n,) i32 — step banned at, -1 if active
    ban_reason: jnp.ndarray  # (n,) i32 — BAN_* code
    accused_count: jnp.ndarray  # (n,) i32 — accusation ledger (cumulative)
    last_checked: jnp.ndarray  # (n,) i32 — step last audited by a validator
    col_checked: jnp.ndarray  # (n,) i32 — step each digest COLUMN was last
    # broadcast/audited (sampled-digest mode's staleness ledger; all
    # columns every step when sampling is off)
    delay_buf: jnp.ndarray  # (D, n, d) — ring buffer for delayed attack
    # (D = cfg.delay_depth: 0 rows unless the attack is delayed_gradient)
    # --- elastic membership (core.sybil) ---
    lifecycle: jnp.ndarray  # (n,) i32 — SLOT_* code per slot
    slot_identity: jnp.ndarray  # (n,) i32 — identity occupying each slot
    probation_clean: jnp.ndarray  # (n,) i32 — consecutive clean spot-checks
    events: jnp.ndarray  # (n_events, 4) i32 — [step, kind, slot, identity]
    id_ban_step: jnp.ndarray  # (n_ids,) i32 — identity ban ledger, -1 clean
    id_ban_reason: jnp.ndarray  # (n_ids,) i32 — BAN_* per identity
    id_accused: jnp.ndarray  # (n_ids,) i32 — per-identity accusation ledger


class StepOutputs(NamedTuple):
    """Per-step observables (stacked along the leading axis under scan)."""

    g_hat: jnp.ndarray  # (d,) the robust aggregate
    seed: jnp.ndarray  # () i32 — the step's MPRNG output
    banned_now: jnp.ndarray  # (n,) bool
    ban_reason_now: jnp.ndarray  # (n,) i32
    accuse_mat: jnp.ndarray  # (n, n) bool — accuser x target (peers)
    sys_accuse: jnp.ndarray  # (n,) bool — checksum / Delta_max accusations
    cheated: jnp.ndarray  # (n,) bool — recompute verdict per peer
    checksum_violations: jnp.ndarray  # () i32
    check_averaging: jnp.ndarray  # () i32
    n_active: jnp.ndarray  # () i32 — active count at step start
    validators: jnp.ndarray  # (n,) f32 — this step's validator mask
    clip_iters_used: jnp.ndarray  # () i32 — max CenteredClip iterations any
    # partition ran (== cfg.clip_iters on the fixed path; the adaptive
    # early-exit's actual budget otherwise)
    sampled_parts: jnp.ndarray  # (n,) bool — digest columns broadcast this
    # step (all-True when sampled-digest mode is off)
    lifecycle: jnp.ndarray  # (n,) i32 — post-step SLOT_* code per slot


@dataclass(frozen=True)
class EngineConfig:
    """Static (hashable) protocol configuration — one jit cache entry per
    distinct config; everything dynamic lives in ProtocolState."""

    n: int
    d: int
    tau: float = 1.0
    clip_iters: int = 60
    m_validators: int = 1
    delta_max: float | None = None
    clip_lambda: float | None = None
    # attack switches (core.protocol.AttackConfig, flattened)
    attack: str = "none"
    start_step: int = 0
    end_step: int = 10**9
    lam: float = 1000.0
    delay: int = 1000
    aggregator_attack: bool = False
    aggregator_scale: float = 0.0
    misreport_s: bool = True
    false_accuse: bool = False
    mprng_abort: bool = False
    # engine switches
    warm_start: bool = False  # v0 = previous aggregate (fewer clip iters)
    use_pallas: bool = False
    # adaptive CenteredClip: stop when ||v_{l+1}-v_l|| <= adaptive_tol, with
    # clip_iters as the static cap. None = fixed budget. tol=0.0 reproduces
    # the fixed-budget aggregates bitwise (shared update rule).
    adaptive_tol: float | None = None
    # which robust aggregator runs the aggregation phase: an AggregatorSpec,
    # a "name[:k=v,...]" string, or None for the flagship ButterflyClip.
    # The legacy knobs above (tau/clip_iters/warm_start/adaptive_tol) act as
    # DEFAULTS for the spec's declared params; explicit spec params win.
    # Non-verifiable specs (mean, krum, ...) degrade the verification /
    # accusation / ban phases to no-ops — see core.aggregators.
    aggregator: "agg_mod.AggregatorSpec | str | None" = None
    # --- flat-cost verification at scale (core.hierarchy) ---
    # sampled-digest audit mode: the m validators jointly audit
    # m * audit_k digest COLUMNS per step (top-k by audit age + U(0,1)
    # from the step's MPRNG key — unpredictable, recomputable, staleness-
    # bounded), so table broadcast is O(n*k) instead of O(n^2).
    # None = full Alg. 6 tables. Verifiable specs only.
    audit_k: int | None = None
    # hierarchical butterfly-of-butterflies: peers split into `groups`
    # groups of n/groups; level-1 butterfly + gs x gs tables inside each
    # group, linear level-2 combine across groups with its own g x g
    # digest exchange (always-on zero-sum checksum). None/1 = flat.
    groups: int | None = None
    # --- elastic membership (core.sybil) ---
    # capacity of the device-resident join/leave event table threaded
    # through the scan; 0 = fixed peer set (every existing config), the
    # fast path that skips all membership machinery.
    n_events: int = 0
    # consecutive clean public-seed spot-checks a joining peer must pass
    # before its slot flips probation -> active (App. F probation window)
    probation_steps: int = 4
    # identity-ledger capacity; 0 = n + n_events (every event can
    # introduce at most one fresh identity)
    max_identities: int = 0

    def __post_init__(self):
        if self.audit_k is not None and self.audit_k < 1:
            raise ValueError("audit_k must be >= 1 (None = full tables)")
        if self.groups is not None and self.groups > 1:
            hier_mod.group_shape(self.n, self.groups)  # validates n % g
        if self.n_events < 0 or self.probation_steps < 1:
            raise ValueError("n_events >= 0 and probation_steps >= 1")

    @property
    def hierarchical(self) -> bool:
        return self.groups is not None and self.groups > 1

    @property
    def elastic(self) -> bool:
        return self.n_events > 0

    @property
    def n_ids(self) -> int:
        return max(self.max_identities, self.n + self.n_events)

    def agg_spec(self) -> "agg_mod.AggregatorSpec":
        """The resolved aggregator spec (legacy knobs filled as defaults).
        ``clip_iters`` is the uniform iteration-budget knob: it fills
        ``n_iters`` (fixed-budget specs) AND ``max_iters`` (to-tolerance
        specs) — set e.g. ``centered_clip:max_iters=200`` explicitly to
        restore the paper's run-to-convergence baseline."""
        return agg_mod.resolve_spec(self.aggregator).with_defaults(
            tau=self.tau, n_iters=self.clip_iters,
            max_iters=self.clip_iters,
            adaptive_tol=self.adaptive_tol, warm_start=self.warm_start,
        )

    @property
    def n_parts(self) -> int:
        return self.n

    @property
    def part(self) -> int:
        return bf.pad_to_parts(self.d, self.n) // self.n

    @property
    def has_gradient_attack(self) -> bool:
        return self.attack not in ("none", "label_flip")

    @property
    def has_any_attack(self) -> bool:
        return (
            self.attack != "none"
            or self.aggregator_attack
            or self.false_accuse
            or self.mprng_abort
        )

    @property
    def delay_depth(self) -> int:
        """Rows of gradient history the state carries: the delayed_gradient
        attack's delay, and 0 for every other run."""
        return max(1, self.delay) if self.attack == "delayed_gradient" else 0


def config_from_attack(n, d, attack, **kw) -> EngineConfig:
    """Build an EngineConfig from a core.protocol.AttackConfig plus the
    protocol kwargs (tau, clip_iters, ...)."""
    return EngineConfig(
        n=n,
        d=d,
        attack=attack.kind,
        start_step=attack.start_step,
        end_step=attack.end_step,
        lam=attack.lam,
        delay=attack.delay,
        aggregator_attack=attack.aggregator_attack,
        aggregator_scale=attack.aggregator_scale,
        misreport_s=attack.misreport_s,
        false_accuse=attack.false_accuse,
        mprng_abort=attack.mprng_abort,
        **kw,
    )


def encode_events(cfg: EngineConfig, schedule) -> jnp.ndarray:
    """Encode a host-side churn schedule into the statically-shaped
    ``(cfg.n_events, 4)`` i32 event table carried in :class:`ProtocolState`.

    ``schedule``: iterable of ``(step, kind, slot)`` / ``(step, kind, slot,
    identity)`` tuples (kind ``"join"``/``"leave"`` or EVENT_* code) or
    :class:`repro.core.sybil.MembershipEvent`. A join WITHOUT an explicit
    identity gets a fresh one (``n``, ``n+1``, ... in schedule order) — the
    rejoin-under-new-key model; passing the identity of a previously banned
    peer is the same-key rejoin, re-banned at admission from the identity
    ledger. Events are sorted by (step, leaves-first) so a leave+join on
    the same slot at the same step is a handoff; unused rows are padded
    inert (step -1 never fires).
    """
    kind_codes = {"join": EVENT_JOIN, "leave": EVENT_LEAVE,
                  EVENT_JOIN: EVENT_JOIN, EVENT_LEAVE: EVENT_LEAVE}
    rows, next_id = [], cfg.n
    for ev in schedule:
        if isinstance(ev, sybil_mod.MembershipEvent):
            ev = (ev.step, ev.kind, ev.slot)
        step, kind, slot = ev[0], kind_codes[ev[1]], ev[2]
        if not 0 <= slot < cfg.n:
            raise ValueError(f"event slot {slot} outside [0, {cfg.n})")
        if kind == EVENT_JOIN:
            ident = ev[3] if len(ev) > 3 else next_id
            next_id = max(next_id, ident + 1)
            if not 0 <= ident < cfg.n_ids:
                raise ValueError(
                    f"identity {ident} outside [0, {cfg.n_ids}); raise "
                    "EngineConfig.max_identities"
                )
        else:
            ident = -1
        rows.append((int(step), int(kind), int(slot), int(ident)))
    if len(rows) > cfg.n_events:
        raise ValueError(
            f"{len(rows)} events > EngineConfig.n_events={cfg.n_events}"
        )
    rows.sort(key=lambda r: (r[0], 0 if r[1] == EVENT_LEAVE else 1))
    rows += [(-1, EVENT_NONE, 0, -1)] * (cfg.n_events - len(rows))
    return jnp.asarray(rows, jnp.int32).reshape(cfg.n_events, 4)


def init_state(cfg: EngineConfig, seed: int = 0, events=None,
               vacant=()) -> ProtocolState:
    """Initial protocol state. ``events``: a churn schedule (anything
    :func:`encode_events` accepts, or an already-encoded (n_events, 4)
    array). ``vacant``: slots that start unoccupied (capacity reclaimed by
    later join events)."""
    n = cfg.n
    # bf16: the buffer only feeds the delayed ATTACK rows (they mismatch
    # honest_G regardless), and it is the one O(delay·n·d) carry; every
    # other attack carries it empty
    buf_dtype = jnp.bfloat16 if cfg.delay_depth > 1 else jnp.float32
    buf_bytes = cfg.delay_depth * n * cfg.d * jnp.dtype(buf_dtype).itemsize
    if buf_bytes > 2**29:  # > 0.5 GiB carried through every step
        raise ValueError(
            f"delayed_gradient ring buffer would be (delay={cfg.delay}, "
            f"n={n}, d={cfg.d}) = {buf_bytes / 2**30:.1f} GiB of scan "
            "carry; set AttackConfig.delay to the actual delay you want "
            "(typical runs use 5-50 — the legacy host buffer grew lazily, "
            "the engine's is dense)"
        )
    lifecycle = jnp.full((n,), SLOT_ACTIVE, jnp.int32)
    slot_identity = jnp.arange(n, dtype=jnp.int32)
    for s in vacant:
        lifecycle = lifecycle.at[int(s)].set(SLOT_VACANT)
        slot_identity = slot_identity.at[int(s)].set(-1)
    active0 = (lifecycle == SLOT_ACTIVE).astype(jnp.float32)
    if events is None:
        ev = jnp.full((cfg.n_events, 4), -1, jnp.int32)
    elif isinstance(events, (jnp.ndarray,)) or (
        hasattr(events, "shape") and getattr(events, "ndim", 0) == 2
    ):
        ev = jnp.asarray(events, jnp.int32)
        if ev.shape != (cfg.n_events, 4):
            raise ValueError(
                f"events shape {ev.shape} != ({cfg.n_events}, 4)"
            )
    else:
        ev = encode_events(cfg, events)
    key = jax.random.PRNGKey(seed)
    # elect step-0 validators from the same chain the steps use (fold at -1)
    validator = _elect(cfg, jax.random.fold_in(key, 2**31 - 1), active0)
    return ProtocolState(
        step=jnp.asarray(0, jnp.int32),
        key=key,
        active=active0,
        validator=validator,
        prev_agg=jnp.zeros((cfg.n_parts, cfg.part), jnp.float32),
        ban_step=jnp.full((n,), -1, jnp.int32),
        ban_reason=jnp.zeros((n,), jnp.int32),
        accused_count=jnp.zeros((n,), jnp.int32),
        last_checked=jnp.full((n,), -1, jnp.int32),
        col_checked=jnp.full((n,), -1, jnp.int32),
        delay_buf=jnp.zeros((cfg.delay_depth, n, cfg.d), buf_dtype),
        lifecycle=lifecycle,
        slot_identity=slot_identity,
        probation_clean=jnp.zeros((n,), jnp.int32),
        events=ev,
        id_ban_step=jnp.full((cfg.n_ids,), -1, jnp.int32),
        id_ban_reason=jnp.zeros((cfg.n_ids,), jnp.int32),
        id_accused=jnp.zeros((cfg.n_ids,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Phase functions — each a pure map over (cfg, state fragments)
# ---------------------------------------------------------------------------
def _attacking(cfg: EngineConfig, t):
    if not cfg.has_any_attack:
        return jnp.asarray(False)
    return (t >= cfg.start_step) & (t < cfg.end_step)


def _phase_key(state: ProtocolState, phase: int):
    return jax.random.fold_in(jax.random.fold_in(state.key, state.step), phase)


def flip_mask(cfg: EngineConfig, state: ProtocolState, byz_mask):
    """Peers whose gradients are computed with flipped labels this step
    (LABEL FLIP happens at gradient time — feed this to ``grads_fn``).
    Probation rows flip too: their public-seed work is what the Sybil gate
    spot-checks, so the attack must be allowed to land there."""
    if cfg.attack != "label_flip":
        return jnp.zeros((cfg.n,), bool)
    engaged = (state.active > 0) | (state.lifecycle == SLOT_PROBATION)
    return _attacking(cfg, state.step) & (byz_mask > 0) & engaged


def phase_membership(cfg: EngineConfig, state: ProtocolState) -> ProtocolState:
    """Fire this step's join/leave events (the device-resident schedule in
    ``state.events``) before the round runs.

    Leave: the slot goes vacant; the SLOT ledgers (ban_step/ban_reason/
    accused_count/probation_clean) describe the occupant, so they reset with
    it — the occupant's history lives on in the identity ledgers (id_*),
    which membership never touches. Join: only onto a vacant slot; the
    incoming identity's history is restored from the identity ledgers — a
    previously banned identity (same-key rejoin) lands directly in BANNED,
    anyone else starts PROBATION at zero clean checks. ``col_checked`` /
    ``last_checked`` are column/audit staleness, a property of the
    topology, not the occupant — churn leaves them alone.

    Events are applied in row order (encode_events sorts step-then-
    leaves-first); a row whose step != t, or whose precondition fails
    (leave of a vacant slot, join onto an occupied one), is a no-op via
    out-of-range scatter drop.
    """
    if not cfg.elastic:
        return state
    n = cfg.n
    lifecycle, slot_identity = state.lifecycle, state.slot_identity
    clean, accused = state.probation_clean, state.accused_count
    ban_step, ban_reason = state.ban_step, state.ban_reason
    for e in range(cfg.n_events):  # static unroll — n_events is small
        ev = state.events[e]
        fire = ev[0] == state.step
        kind, slot, ident = ev[1], ev[2], ev[3]
        slot_c = jnp.clip(slot, 0, n - 1)
        ident_c = jnp.clip(ident, 0, cfg.n_ids - 1)

        do_leave = fire & (kind == EVENT_LEAVE) & (
            lifecycle[slot_c] != SLOT_VACANT
        )
        ls = jnp.where(do_leave, slot_c, n)  # n = out of range -> drop
        lifecycle = lifecycle.at[ls].set(SLOT_VACANT, mode="drop")
        slot_identity = slot_identity.at[ls].set(-1, mode="drop")
        clean = clean.at[ls].set(0, mode="drop")
        accused = accused.at[ls].set(0, mode="drop")
        ban_step = ban_step.at[ls].set(-1, mode="drop")
        ban_reason = ban_reason.at[ls].set(BAN_NONE, mode="drop")

        do_join = fire & (kind == EVENT_JOIN) & (
            lifecycle[slot_c] == SLOT_VACANT
        )
        pre_banned = state.id_ban_step[ident_c] >= 0
        js = jnp.where(do_join, slot_c, n)
        lifecycle = lifecycle.at[js].set(
            jnp.where(pre_banned, SLOT_BANNED, SLOT_PROBATION), mode="drop"
        )
        slot_identity = slot_identity.at[js].set(ident_c, mode="drop")
        clean = clean.at[js].set(0, mode="drop")
        accused = accused.at[js].set(state.id_accused[ident_c], mode="drop")
        ban_step = ban_step.at[js].set(
            jnp.where(pre_banned, state.id_ban_step[ident_c], -1),
            mode="drop",
        )
        ban_reason = ban_reason.at[js].set(
            jnp.where(pre_banned, state.id_ban_reason[ident_c], BAN_NONE),
            mode="drop",
        )
    active = (lifecycle == SLOT_ACTIVE).astype(jnp.float32)
    return state._replace(
        lifecycle=lifecycle, slot_identity=slot_identity,
        probation_clean=clean, accused_count=accused,
        ban_step=ban_step, ban_reason=ban_reason,
        active=active, validator=state.validator * active,
    )


def phase_attack(cfg: EngineConfig, state: ProtocolState, G, honest_G, byz,
                 engage_b=None):
    """apply_attack: Byzantine rows swap in their attack vectors; the delay
    ring buffer rotates; honest peers optionally self-clip (Alg. 9).
    ``engage_b`` widens the attacked-row mask beyond the active set (the
    elastic path includes probation rows, so the Sybil spot-check sees the
    attack); defaults to the active mask."""
    t = state.step
    att = _attacking(cfg, t)
    active_b = state.active > 0 if engage_b is None else engage_b
    delay_buf = state.delay_buf

    if cfg.has_gradient_attack:
        delayed = None
        if cfg.delay_depth:
            # written at t - delay_depth (zeros before)
            delayed = delay_buf[t % cfg.delay_depth].astype(jnp.float32)
        G = attacks_mod.apply_attack(
            attacks_mod.attack_index(cfg.attack),
            G,
            byz & active_b & att,
            key=_phase_key(state, 1),
            lam=cfg.lam,
            delayed=delayed,
            hon_mask=~byz & active_b,
        )
    # history for the delayed attack (honest rows of byzantine peers)
    if cfg.delay_depth:
        slot = t % cfg.delay_depth
        delay_buf = delay_buf.at[slot].set(
            jnp.where((byz & active_b)[:, None], honest_G, 0.0).astype(
                delay_buf.dtype
            )
        )

    if cfg.clip_lambda is not None:  # BTARD-Clipped-SGD (Alg. 9, honest peers)
        nrm = jnp.linalg.norm(G, axis=1)
        scale = jnp.minimum(1.0, cfg.clip_lambda / jnp.maximum(nrm, 1e-30))
        clip_rows = (~byz)[:, None]
        G = jnp.where(clip_rows, G * scale[:, None], G)
        honest_G = jnp.where(clip_rows, G, honest_G)
    return G, honest_G, delay_buf


def phase_mprng(cfg: EngineConfig, state: ProtocolState, byz):
    """MPRNG: the shared seed plus the abort-ban outcome. The commit/reveal
    transcript (core.mprng) collapses to an unbiased draw; a Byzantine
    aborter (trying the learn-early-and-abort bias) is banned — here modelled
    as: when the abort-bias attack is on and the candidate draw has the
    parity the attacker dislikes, every attacking peer aborts and is banned."""
    seed = jax.random.randint(
        _phase_key(state, 0), (), 0, jnp.int32(2**31 - 1), jnp.int32
    )
    mprng_ban = jnp.zeros((cfg.n,), bool)
    if cfg.mprng_abort:
        abort = (seed % 2 == 1) & _attacking(cfg, state.step)
        mprng_ban = abort & byz & (state.active > 0)
    return seed, mprng_ban


def _scatter_cols(values, idx, n, n_cols):
    """Scatter (n, k) sampled-column tables into zero (n, n_cols) tables.
    Unsampled columns are identically zero on BOTH the reported and the
    recomputed side, so every downstream mismatch/checksum/vote term is
    silent there by construction — no masking plumbing anywhere else."""
    return jnp.zeros((n, n_cols), jnp.float32).at[:, idx].set(values)


def phase_aggregation(cfg: EngineConfig, state: ProtocolState, G, weights,
                      seed, samp_idx=None):
    """Spec-dispatched robust aggregation (``cfg.aggregator``).

    Verifiable specs — the ButterflyClip flagship (per-partition
    CenteredClip + tau-clipped residual tables, optionally warm-started
    and/or adaptive) and the ``verified:<base>`` wrappers over the
    coordinatewise baselines (base aggregation + generalized contribution
    digests, ``core.verification``) — run via
    :func:`verification.spec_aggregate`. The tables/digests are always
    computed exactly once against the final aggregate, so downstream
    accusation semantics never see the iteration budget.

    Non-verifiable specs (mean, median, Krum, ...): the flat registry fn
    runs over the stacked gradients; there are no broadcast tables
    (z/s_tbl/norm_tbl come back None) and the caller degrades the
    verification/accusation phases to no-ops.

    Returns (agg (n_parts, part), parts, z, s_tbl, norm_tbl, iters_used).
    """
    spec = cfg.agg_spec()
    if not spec.verifiable:
        agg_fn = spec.build(cfg.n, cfg.d, use_pallas=cfg.use_pallas)
        v0 = None
        if spec.warm_startable and spec.get("warm_start", False):
            v0 = jnp.where(
                state.step > 0, bf.merge_parts(state.prev_agg, cfg.d), 0.0
            )
        flat, info = agg_fn(
            G, weights if spec.weighted else None, v0, _phase_key(state, 2)
        )
        # keep the butterfly partition layout for the prev_agg carry
        agg = bf.split_parts(
            flat.astype(jnp.float32)[None, :], cfg.n_parts
        )[0]
        parts = bf.split_parts(G, cfg.n_parts)
        return (agg, parts, None, None, None,
                jnp.asarray(info.iters, jnp.int32))

    z = bf.get_random_directions(seed, cfg.n_parts, cfg.part)
    v0 = None
    if spec.warm_startable and spec.get("warm_start", False):
        v0 = jnp.where(state.step > 0, state.prev_agg, 0.0)
    if cfg.aggregator_attack and cfg.aggregator_scale > 0:
        # tables must be computed against the (possibly corrupted) received
        # aggregate, so aggregation and tables split into two calls here
        agg, parts, _s, _n, iters_used = verif_mod.spec_aggregate(
            spec, G, z=None, weights=weights, v0=v0,
            use_pallas=cfg.use_pallas,
        )
        return agg, parts, z, None, None, iters_used
    if samp_idx is not None:
        # sampled-digest mode: aggregate WITHOUT the fused table epilogue,
        # then digest only the k sampled columns (one O(n*k*part) pass —
        # the scalar-prefetch rows kernel under use_pallas) and scatter
        # them into zero tables
        agg, parts, _s, _n, iters_used = verif_mod.spec_aggregate(
            spec, G, z=None, weights=weights, v0=v0,
            use_pallas=cfg.use_pallas,
        )
        s_r, n_r = verif_mod.digest_tables_rows(
            spec, parts, agg, z, samp_idx, use_pallas=cfg.use_pallas
        )
        s_tbl = _scatter_cols(s_r, samp_idx, cfg.n, cfg.n_parts)
        norm_tbl = _scatter_cols(n_r, samp_idx, cfg.n, cfg.n_parts)
        return agg, parts, z, s_tbl, norm_tbl, iters_used
    agg, parts, s_tbl, norm_tbl, iters_used = verif_mod.spec_aggregate(
        spec, G, z=z, weights=weights, v0=v0, use_pallas=cfg.use_pallas,
    )
    return agg, parts, z, s_tbl, norm_tbl, iters_used


def phase_aggregator_attack(cfg, state, agg, parts, z, byz, weights,
                            samp_idx=None):
    """Byzantine aggregators corrupt their partitions; every honest peer
    then reports tables against the corrupted value it received, and one
    colluder cancels the Verification-2 checksum (App. C). The recomputed
    tables are spec-aware: clipped residuals for butterfly_clip, plain
    contribution digests for verified:* wrapped specs. Under sampled-digest
    mode only the sampled columns exist (zero-scattered like the honest
    path), so a corrupted unsampled column goes unnoticed until its
    staleness-bounded turn — the property the coverage tests pin down."""
    honest_agg = agg
    corrupt = jnp.zeros((cfg.n_parts,), bool)
    if cfg.aggregator_attack and cfg.aggregator_scale > 0:
        att = _attacking(cfg, state.step)
        corrupt = byz & (state.active > 0) & att
        agg = attacks_mod.aggregator_shift_all(
            agg, corrupt, _phase_key(state, 3), cfg.aggregator_scale
        )
        if samp_idx is not None:
            s_r, n_r = verif_mod.digest_tables_rows(
                cfg.agg_spec(), parts, agg, z, samp_idx,
                use_pallas=cfg.use_pallas,
            )
            s_tbl = _scatter_cols(s_r, samp_idx, cfg.n, cfg.n_parts)
            norm_tbl = _scatter_cols(n_r, samp_idx, cfg.n, cfg.n_parts)
        else:
            s_tbl, norm_tbl = verif_mod.spec_tables(
                cfg.agg_spec(), parts, agg, z, use_pallas=cfg.use_pallas
            )
    else:
        s_tbl = norm_tbl = None
    return agg, honest_agg, corrupt, s_tbl, norm_tbl


def phase_misreport(cfg, s_tbl, corrupt, byz, active, weights):
    """The first active colluder cancels sum_i w_i s_i^j for each corrupted
    partition j (exactly the legacy protocol's liar selection)."""
    if not (cfg.aggregator_attack and cfg.misreport_s):
        return s_tbl
    is_liar_cand = byz & (active > 0)
    liar = jnp.argmax(is_liar_cand)  # first active byzantine row
    has_liar = is_liar_cand.any()
    w_liar = weights[liar]
    col_sums = (s_tbl * weights[:, None]).sum(0)  # (n_parts,)
    others = col_sums - w_liar * s_tbl[liar]
    lie = -others / jnp.maximum(w_liar, 1e-30)
    new_row = jnp.where(corrupt & has_liar & (w_liar > 0), lie, s_tbl[liar])
    return s_tbl.at[liar].set(new_row)


def _choose_targets(cfg, state, active_b):
    """Audit-age-weighted CHOOSETARGET: the m validators take the m distinct
    candidates with the highest age + U(0,1) score (age = steps since last
    audit), so every active peer is audited at least every ~ceil(n/m) steps
    — the uniform draw's coupon-collector tail is gone — while fresh
    per-step jitter keeps the audit ORDER unpredictable. Targets are
    publicly derivable from the revealed seed (like the paper's
    CHOOSETARGET), so every peer maintains the same last_checked ledger.

    Returns (target (n,) — validator v audits target[v], valid_audit,
    is_validator, target_hot (n, n) bool, audited (n,) bool)."""
    n = cfg.n
    cand = active_b & (state.validator <= 0)
    n_cand = cand.sum()
    u = jax.random.uniform(_phase_key(state, 5), (n,))
    age = (state.step - state.last_checked).astype(jnp.float32)
    score = jnp.where(cand, age + u, -jnp.inf)
    order = jnp.argsort(-score)  # candidate peer ids by audit priority
    is_validator = (state.validator > 0) & active_b
    val_ord = jnp.clip(jnp.cumsum(is_validator) - 1, 0, n - 1)
    target = order[val_ord]  # (n,) — validator v audits target[v]
    valid_audit = is_validator & (val_ord < n_cand)
    target_hot = jax.nn.one_hot(target, n, dtype=bool)
    audited = (target_hot & valid_audit[:, None]).any(axis=0)
    return target, valid_audit, is_validator, target_hot, audited


def phase_verify(cfg, state, G, honest_G, agg, honest_agg, parts, s_tbl,
                 true_s, norm_tbl, true_norm, byz, weights):
    """Verifications 1-3 + validator spot checks -> accusation matrices."""
    n = cfg.n
    active_b = state.active > 0
    att = _attacking(cfg, state.step)

    tol_norm = 1e-4 * (1.0 + true_norm)
    tol_s = 1e-4 * (1.0 + jnp.abs(true_s))
    mismatch_norm = jnp.abs(norm_tbl - true_norm) > tol_norm  # (peer, part)
    mismatch_s = jnp.abs(s_tbl - true_s) > tol_s

    # V1 + V2a: honest aggregator j accuses any i misreporting for col j
    agg_ok = active_b & ~byz  # byzantine aggregators stay silent
    accuse = agg_ok[:, None] & (mismatch_norm | mismatch_s).T  # (j, i)

    # V2b: global checksum per partition (system accusation on the owner).
    # The zero-sum identity only holds when the digest combines LINEARLY
    # into the aggregate (the CenteredClip fixed point / the weighted mean)
    # — for nonlinear verified:* wrapped specs (median, trimmed mean) it is
    # statically disabled, so honest runs stay accusation-free; a lying
    # aggregator is caught by the validator partition recompute below.
    if verif_mod.has_zero_checksum(cfg.agg_spec()):
        cs_tol = bf.checksum_tolerance(agg, parts)
        sums = (s_tbl * weights[:, None]).sum(0)
        sys_accuse = jnp.abs(sums) > cs_tol
    else:
        sys_accuse = jnp.zeros((n,), bool)
    checksum_violations = sys_accuse.sum().astype(jnp.int32)

    # V3: Delta_max majority vote -> CHECKAVERAGING(j)
    check_averaging = jnp.asarray(0, jnp.int32)
    if cfg.delta_max is not None:
        votes = ((true_norm > cfg.delta_max) * weights[:, None]).sum(0)
        v3 = votes > weights.sum() / 2.0
        check_averaging = v3.sum().astype(jnp.int32)
        sys_accuse = sys_accuse | v3

    # validator spot checks — audit-age-weighted CHOOSETARGET
    # (:func:`_choose_targets`, shared with the hierarchical core)
    target, valid_audit, is_validator, target_hot, audited = _choose_targets(
        cfg, state, active_b
    )

    grad_mismatch = jnp.any(G != honest_G, axis=1)  # commitment recompute
    row_tol = 1e-4 * (1.0 + jnp.abs(true_s).max(axis=1))
    s_row_mismatch = jnp.abs(s_tbl - true_s).max(axis=1) > row_tol
    # CheckComputations covers the audited peer's FULL work: its gradient,
    # its reported table row AND its partition aggregation (peer j owns
    # partition j, Alg. 2) — the recompute that catches a lying aggregator
    # even for wrapped specs whose checksum identity (V2b) does not exist.
    agg_mismatch = jnp.any(agg != honest_agg, axis=1)  # (n_parts,) == (n,)

    caught = (grad_mismatch[target] | s_row_mismatch[target]
              | agg_mismatch[target])
    val_accuse = is_validator & ~byz & caught & valid_audit
    if cfg.false_accuse:
        val_accuse = val_accuse | (is_validator & byz & att & valid_audit)
    accuse = accuse | (target_hot & val_accuse[:, None])
    last_checked = jnp.where(audited, state.step, state.last_checked)

    # accusations only flow between active peers
    accuse = accuse & active_b[:, None] & active_b[None, :]
    sys_accuse = sys_accuse & active_b
    return (accuse, sys_accuse, mismatch_s, checksum_violations,
            check_averaging, last_checked)


def phase_accuse_ban(cfg, state, accuse, sys_accuse, mismatch_s, mprng_ban,
                     G, honest_G, agg, honest_agg, s_tbl, true_s,
                     norm_tbl, true_norm):
    """ACCUSE resolution (Alg. 4): everyone recomputes the accused peer's
    work from the public seed; the guilty party is the target if the
    accusation holds (plus everyone who covered it up), else the accuser."""
    active_b = state.active > 0

    cheated = (
        jnp.any(G != honest_G, axis=1)  # gradient attack
        | jnp.any(  # s misreport
            jnp.abs(s_tbl - true_s) > 1e-5 + 1e-3 * jnp.abs(true_s), axis=1
        )
        | jnp.any(  # norm misreport
            jnp.abs(norm_tbl - true_norm) > 1e-5 + 1e-3 * jnp.abs(true_norm),
            axis=1,
        )
        | jnp.any(agg != honest_agg, axis=1)  # aggregation attack (owner j)
    )

    accused = sys_accuse | accuse.any(axis=0)
    ban_cheater = accused & cheated & active_b
    # Alg. 4 L11-13: peers whose reported s for a guilty peer's partition
    # mismatches the recomputed value covered for it -> banned too
    ban_coverup = (mismatch_s & ban_cheater[None, :]).any(axis=1) & active_b
    # Hammurabi: accusing a peer the recompute exonerates bans the accuser
    ban_false = (accuse & ~cheated[None, :]).any(axis=1) & active_b

    banned_now = ban_cheater | ban_coverup | ban_false | (mprng_ban & active_b)
    reason = jnp.where(
        ban_cheater, BAN_CHEATER,
        jnp.where(ban_coverup, BAN_COVERUP,
                  jnp.where(ban_false, BAN_FALSE_ACCUSER,
                            jnp.where(mprng_ban, BAN_MPRNG, BAN_NONE))),
    ).astype(jnp.int32)
    reason = jnp.where(banned_now, reason, BAN_NONE)

    new_active = state.active * (1.0 - banned_now)
    return new_active, banned_now, reason, cheated, accused.astype(jnp.int32)


def phase_hier(cfg, state, byz, weights, seed, G, G_cmp, honest_G_cmp,
               samp_mask, mprng_ban):
    """The hierarchical butterfly-of-butterflies verifiable core:
    aggregation + aggregator attack + misreport + verify + accuse/ban in
    the two-level topology (core.hierarchy).

    Level 1: each group of gs = n/groups peers runs the full spec over its
    own butterfly — tables are gs x gs PER GROUP, broadcast within the
    group only. Level 2: the linear leader combine with its always-on
    zero-sum checksum; a violated super-partition implicates its group's
    leader, so bans propagate through the group digests. Accusations stay
    peer x peer (n, n) — level-1 blocks scatter block-diagonally — so
    :func:`phase_accuse_ban` and the whole ban machinery run unchanged
    over the hier shapes. ``samp_mask`` (n,) composes the sampled-digest
    mode in: global cell (a, c) guards column c of group a's tables
    (cell index == owner peer id, both levels of masking agree).

    Returns the same tail tuple the flat verifiable branch produces, plus
    the global aggregate in the standard (n_parts, part) layout.
    """
    n = cfg.n
    g, gs = hier_mod.group_shape(n, cfg.groups)
    active = state.active
    active_b = active > 0
    att = _attacking(cfg, state.step)
    spec = cfg.agg_spec()

    attacking_agg = bool(cfg.aggregator_attack and cfg.aggregator_scale > 0)
    v0_flat = None
    if spec.warm_startable and spec.get("warm_start", False):
        v0_flat = jnp.where(
            state.step > 0, bf.merge_parts(state.prev_agg, cfg.d), 0.0
        )
    h = hier_mod.hier_aggregate(
        spec, G, weights, seed, cfg.groups, v0_flat=v0_flat,
        with_tables=not attacking_agg,
    )
    u, s1, norms1 = h.u, h.s1, h.norms1
    part1 = u.shape[-1]
    corrupt = jnp.zeros((n,), bool)
    if attacking_agg:
        # cell (a, r) of the level-1 aggregate is owned by peer a*gs + r,
        # so the flat (n,)-masked shift applies to the (n, part1) reshape
        corrupt = byz & active_b & att
        u = attacks_mod.aggregator_shift_all(
            u.reshape(n, part1), corrupt, _phase_key(state, 3),
            cfg.aggregator_scale,
        ).reshape(u.shape)
        s1, norms1 = hier_mod.hier_tables(spec, h.parts1, u, h.z1)

    wg = weights.reshape(g, gs)
    if samp_mask is not None:
        samp_h = samp_mask.reshape(g, gs)
        s1 = jnp.where(samp_h[:, None, :], s1, 0.0)
        norms1 = jnp.where(samp_h[:, None, :], norms1, 0.0)
    true_s1, true_norm1 = s1, norms1
    # per-group misreport: each group's first active colluder cancels its
    # group's checksum for the corrupted columns (vmapped flat phase)
    s1 = jax.vmap(
        lambda s, c, b, a, w: phase_misreport(cfg, s, c, b, a, w)
    )(s1, corrupt.reshape(g, gs), byz.reshape(g, gs),
      active.reshape(g, gs), wg)

    # level 2: combine the (possibly corrupted) group aggregates — honest
    # leaders relay faithfully, so reported == recomputed at level 2 and
    # the always-on linear checksum is the alarm that a group-level
    # corruption reached the global aggregate
    lvl2 = hier_mod.level2_combine(u, h.group_w, cfg.d, seed)
    v_flat = bf.merge_parts(lvl2.v2, cfg.d)
    agg_std = bf.split_parts(v_flat[None, :], cfg.n_parts)[0]

    # ---- verify: V1/V2/V3 per group + level-2 checksum + audits ----------
    tol_n1 = 1e-4 * (1.0 + true_norm1)
    tol_s1 = 1e-4 * (1.0 + jnp.abs(true_s1))
    mm_norm = jnp.abs(norms1 - true_norm1) > tol_n1  # (g, peer_r, col_c)
    mm_s = jnp.abs(s1 - true_s1) > tol_s1

    idx = jnp.arange(n).reshape(g, gs)
    agg_ok_g = (active_b & ~byz).reshape(g, gs)
    acc_blocks = agg_ok_g[:, :, None] & jnp.swapaxes(mm_norm | mm_s, 1, 2)
    accuse = jnp.zeros((n, n), bool).at[
        idx[:, :, None], idx[:, None, :]
    ].set(acc_blocks)
    mismatch_s = jnp.zeros((n, n), bool).at[
        idx[:, :, None], idx[:, None, :]
    ].set(mm_s)

    if verif_mod.has_zero_checksum(spec):
        cs_tol = jax.vmap(bf.checksum_tolerance)(u, h.parts1)  # (g,)
        sums1 = (s1 * wg[:, :, None]).sum(1)  # (g, gs) per group column
        sys_accuse = (jnp.abs(sums1) > cs_tol[:, None]).reshape(n)
    else:
        sys_accuse = jnp.zeros((n,), bool)
    cs2_tol = bf.checksum_tolerance(lvl2.v2, lvl2.parts2)
    sums2 = (lvl2.s2 * h.group_w[:, None]).sum(0)  # (g,)
    leader_accuse = jnp.zeros((n,), bool).at[jnp.arange(g) * gs].set(
        jnp.abs(sums2) > cs2_tol
    )
    sys_accuse = sys_accuse | leader_accuse
    checksum_violations = sys_accuse.sum().astype(jnp.int32)

    check_averaging = jnp.asarray(0, jnp.int32)
    if cfg.delta_max is not None:
        # group-majority Delta_max vote over the group's weight mass
        votes = ((true_norm1 > cfg.delta_max) * wg[:, :, None]).sum(1)
        v3 = (votes > wg.sum(axis=1, keepdims=True) / 2.0).reshape(n)
        check_averaging = v3.sum().astype(jnp.int32)
        sys_accuse = sys_accuse | v3

    # validator CHOOSETARGET audit — a FULL-peer recompute, independent of
    # digest sampling and of the topology: the backstop that keeps
    # gradient-attack time-to-ban flat under both axes
    target, valid_audit, is_validator, target_hot, audited = _choose_targets(
        cfg, state, active_b
    )
    grad_mismatch = jnp.any(G_cmp != honest_G_cmp, axis=1)
    s_h, true_s_h = s1.reshape(n, gs), true_s1.reshape(n, gs)
    row_tol = 1e-4 * (1.0 + jnp.abs(true_s_h).max(axis=1))
    s_row_mismatch = jnp.abs(s_h - true_s_h).max(axis=1) > row_tol
    u_n, honest_u_n = u.reshape(n, part1), h.u.reshape(n, part1)
    agg_mismatch = jnp.any(u_n != honest_u_n, axis=1)
    caught = (grad_mismatch[target] | s_row_mismatch[target]
              | agg_mismatch[target])
    val_accuse = is_validator & ~byz & caught & valid_audit
    if cfg.false_accuse:
        val_accuse = val_accuse | (is_validator & byz & att & valid_audit)
    accuse = accuse | (target_hot & val_accuse[:, None])
    last_checked = jnp.where(audited, state.step, state.last_checked)

    accuse = accuse & active_b[:, None] & active_b[None, :]
    sys_accuse = sys_accuse & active_b

    # ---- accuse / ban (the flat machinery over the hier shapes) ----------
    (new_active, banned_now, reason, cheated,
     accused_inc) = phase_accuse_ban(
        cfg, state, accuse, sys_accuse, mismatch_s, mprng_ban,
        G_cmp, honest_G_cmp, u_n, honest_u_n, s_h, true_s_h,
        norms1.reshape(n, gs), true_norm1.reshape(n, gs),
    )
    return (new_active, banned_now, reason, cheated, accused_inc, accuse,
            sys_accuse, checksum_violations, check_averaging, last_checked,
            agg_std, h.iters)


def _elect(cfg: EngineConfig, key, active):
    """Next step's validators: m uniform draws without replacement over the
    active peers, never all of them (Alg. 1 L19 keeps >= 1 contributor)."""
    score = jnp.where(active > 0, jax.random.uniform(key, (cfg.n,)), -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-score))
    m_eff = jnp.minimum(cfg.m_validators, jnp.maximum(active.sum() - 1, 0))
    return ((rank < m_eff) & (active > 0)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# One full protocol step (jit-compilable, scan-compatible)
# ---------------------------------------------------------------------------
def protocol_step(cfg: EngineConfig, state: ProtocolState, byz_mask, G,
                  honest_G):
    """One BTARD-SGD aggregation round as a pure function.

    G / honest_G: (n, d) — honest_G is what a validator recomputing from the
    public seed obtains (equals G except for label-flipped rows). Banned
    rows are zeroed internally, so their supplied values are irrelevant.
    Returns (new_state, StepOutputs).
    """
    spec = cfg.agg_spec()
    byz = jnp.asarray(byz_mask) > 0

    # ---- membership: fire this step's join/leave events ------------------
    state = phase_membership(cfg, state)
    active = state.active
    active_b = active > 0
    prob_b = state.lifecycle == SLOT_PROBATION
    validator = state.validator * active
    if spec.verifiable:
        weights = active * (1.0 - validator)  # Alg. 1 L19: validators sit out
    else:
        # nothing to audit without the broadcast tables: no validator set-
        # aside, every active peer contributes to the aggregate
        weights = active

    # probation rows keep their payloads through the attack phase (the
    # Sybil gate must see what they actually broadcast) but NEVER reach the
    # aggregate or the accusation fabric — they are re-zeroed below.
    keep = (active_b | prob_b)[:, None]
    G = jnp.where(keep, jnp.asarray(G, jnp.float32), 0.0)
    honest_G = jnp.where(keep, jnp.asarray(honest_G, jnp.float32), 0.0)

    # ---- apply_attack ----------------------------------------------------
    G, honest_G, delay_buf = phase_attack(
        cfg, state, G, honest_G, byz, engage_b=active_b | prob_b
    )

    # ---- Sybil probation gate (core.sybil, §3.3 / App. F) ----------------
    # every probation row is spot-checked EVERY step against the public-
    # seed recompute; one mismatch bans the identity, a full clean window
    # promotes the slot. Structurally upstream of aggregation: a probation
    # payload influences nothing but this check.
    if cfg.elastic:
        prob_mismatch = sybil_mod.probation_check(G, honest_G, prob_b)
    else:
        prob_mismatch = jnp.zeros((cfg.n,), bool)
    probation_clean, promote, sybil_ban = sybil_mod.probation_step(
        prob_b, prob_mismatch, state.probation_clean, cfg.probation_steps
    )
    G = jnp.where(active_b[:, None], G, 0.0)
    honest_G = jnp.where(active_b[:, None], honest_G, 0.0)

    # ---- MPRNG (shared seed + abort bans) --------------------------------
    seed, mprng_ban = phase_mprng(cfg, state, byz)

    # ---- sampled-digest column set (public fold of the step key) ---------
    # cell index == digest column == owner peer id, flat AND hierarchical
    # (hier cell (a, c) = peer a*gs + c), so one (n,) ledger serves both
    sampling = spec.verifiable and cfg.audit_k is not None
    if sampling:
        samp_idx, samp_mask = hier_mod.sample_audit_cells(
            _phase_key(state, 6), state.step, state.col_checked,
            cfg.m_validators, cfg.audit_k, cfg.n,
        )
        col_checked = jnp.where(samp_mask, state.step, state.col_checked)
    else:
        samp_idx, samp_mask = None, None
        col_checked = jnp.full((cfg.n,), state.step, jnp.int32)

    if spec.verifiable and cfg.hierarchical:
        # ---- hierarchical butterfly-of-butterflies core ------------------
        if comp_mod.is_wrapped(spec):
            # wire partitions follow the level-1 butterfly: gs per group
            codec = comp_mod.codec_of(spec)
            gs = cfg.n // cfg.groups
            G_cmp = comp_mod.wire_grads(G, codec, gs)
            honest_G_cmp = comp_mod.wire_grads(honest_G, codec, gs)
        else:
            G_cmp, honest_G_cmp = G, honest_G
        (new_active, banned_now, reason, cheated, accused_inc, accuse,
         sys_accuse, cs_viol, chk_avg, last_checked, agg,
         iters_used) = phase_hier(
            cfg, state, byz, weights, seed, G, G_cmp, honest_G_cmp,
            samp_mask, mprng_ban,
        )
    elif spec.verifiable:
        agg, parts, z, s_tbl, norm_tbl, iters_used = phase_aggregation(
            cfg, state, G, weights, seed, samp_idx
        )
        # compressed:* specs: every peer commits to (and validators
        # recompute) the WIRE payload, not the raw f32 gradient — so the
        # commitment comparisons in verify/accuse must run over the wire
        # projection of both sides. A perturbation below the quantization
        # step neither enters the aggregate nor trips a ban (the wire
        # representation IS the protocol-visible contribution); anything
        # that survives quantization differs on the wire and is caught
        # exactly as before. Honest rows are raw-equal, hence wire-equal:
        # zero honest accusations is structural, not a tolerance.
        if comp_mod.is_wrapped(spec):
            codec = comp_mod.codec_of(spec)
            G_cmp = comp_mod.wire_grads(G, codec, cfg.n_parts)
            honest_G_cmp = comp_mod.wire_grads(honest_G, codec, cfg.n_parts)
        else:
            G_cmp, honest_G_cmp = G, honest_G
        agg, honest_agg, corrupt, s2, n2 = phase_aggregator_attack(
            cfg, state, agg, parts, z, byz, weights, samp_idx
        )
        if s_tbl is None:
            s_tbl, norm_tbl = s2, n2
        true_s, true_norm = s_tbl, norm_tbl
        s_tbl = phase_misreport(cfg, s_tbl, corrupt, byz, active, weights)

        # ---- verify ------------------------------------------------------
        (accuse, sys_accuse, mismatch_s, cs_viol, chk_avg,
         last_checked) = phase_verify(
            cfg, state, G_cmp, honest_G_cmp, agg, honest_agg, parts, s_tbl,
            true_s, norm_tbl, true_norm, byz, weights,
        )

        # ---- accuse / ban ------------------------------------------------
        (new_active, banned_now, reason, cheated,
         accused_inc) = phase_accuse_ban(
            cfg, state, accuse, sys_accuse, mismatch_s, mprng_ban,
            G_cmp, honest_G_cmp, agg, honest_agg, s_tbl, true_s, norm_tbl,
            true_norm,
        )
    else:
        agg, parts, z, s_tbl, norm_tbl, iters_used = phase_aggregation(
            cfg, state, G, weights, seed
        )
        # non-verifiable aggregator: no tables -> no verification, no
        # accusations, no bans (incl. the MPRNG abort rule, which is part
        # of the same commit/reveal machinery). The attack still lands in
        # the aggregate; only the DEFENSE's detection arm is absent.
        n = cfg.n
        accuse = jnp.zeros((n, n), bool)
        sys_accuse = jnp.zeros((n,), bool)
        cheated = jnp.zeros((n,), bool)
        cs_viol = jnp.asarray(0, jnp.int32)
        chk_avg = jnp.asarray(0, jnp.int32)
        last_checked = state.last_checked
        banned_now = jnp.zeros((n,), bool)
        reason = jnp.zeros((n,), jnp.int32)
        accused_inc = jnp.zeros((n,), jnp.int32)
        new_active = active

    # ---- lifecycle transitions (bans + probation promotions) -------------
    # protocol bans (active rows) and sybil bans (probation rows) are
    # disjoint by construction; promote is clean-probation only. In the
    # fixed-membership case promote/sybil_ban are identically False and
    # (new_lifecycle == ACTIVE) reproduces active * (1 - banned_now) bitwise.
    banned_now = banned_now | sybil_ban
    reason = jnp.where(sybil_ban, BAN_SYBIL, reason).astype(jnp.int32)
    new_lifecycle = jnp.where(
        banned_now, SLOT_BANNED,
        jnp.where(promote, SLOT_ACTIVE, state.lifecycle),
    ).astype(jnp.int32)
    new_active = (new_lifecycle == SLOT_ACTIVE).astype(jnp.float32)

    # ---- identity ledgers (persist across leave/rejoin) ------------------
    ident = state.slot_identity
    idc = jnp.clip(ident, 0, cfg.n_ids - 1)
    first_ban = banned_now & (ident >= 0) & (state.id_ban_step[idc] < 0)
    sid = jnp.where(first_ban, idc, cfg.n_ids)  # out of range -> drop
    id_ban_step = state.id_ban_step.at[sid].set(state.step, mode="drop")
    id_ban_reason = state.id_ban_reason.at[sid].set(reason, mode="drop")
    aid = jnp.where(ident >= 0, idc, cfg.n_ids)
    id_accused = state.id_accused.at[aid].add(accused_inc, mode="drop")

    # ---- elect next validators ------------------------------------------
    next_validator = _elect(cfg, _phase_key(state, 4), new_active)

    g_hat = bf.merge_parts(agg, cfg.d)
    # warm-start hygiene: only carry the aggregate forward as v0 when this
    # step's PUBLIC misbehaviour signals were clean — after a ban or a
    # Delta_max vote the aggregate may be corrupted, so the next step
    # cold-starts rather than seeding from it. (The raw checksum is NOT the
    # gate: far from convergence — exactly the small-clip_iters regime warm
    # start enables — its residual legitimately exceeds tolerance. A
    # colluder who cancels the checksum evades this gate; the carried bias
    # stays bounded by the per-step corruption scale — DESIGN.md.)
    clean = ~banned_now.any() & (chk_avg == 0)
    new_state = ProtocolState(
        step=state.step + 1,
        key=state.key,
        active=new_active,
        validator=next_validator,
        prev_agg=jnp.where(clean, agg.astype(jnp.float32), 0.0),
        ban_step=jnp.where(banned_now, state.step, state.ban_step),
        ban_reason=jnp.where(banned_now, reason, state.ban_reason),
        accused_count=state.accused_count + accused_inc,
        last_checked=last_checked,
        col_checked=col_checked,
        delay_buf=delay_buf,
        lifecycle=new_lifecycle,
        slot_identity=state.slot_identity,
        probation_clean=probation_clean,
        events=state.events,
        id_ban_step=id_ban_step,
        id_ban_reason=id_ban_reason,
        id_accused=id_accused,
    )
    out = StepOutputs(
        g_hat=g_hat,
        seed=seed,
        banned_now=banned_now,
        ban_reason_now=reason,
        accuse_mat=accuse,
        sys_accuse=sys_accuse,
        cheated=cheated,
        checksum_violations=cs_viol,
        check_averaging=chk_avg,
        n_active=active.sum().astype(jnp.int32),
        validators=validator,
        clip_iters_used=iters_used,
        sampled_parts=(samp_mask if sampling
                       else jnp.ones((cfg.n,), bool)),
        lifecycle=new_lifecycle,
    )
    return new_state, out


@functools.lru_cache(maxsize=32)
def jit_protocol_step(cfg: EngineConfig):
    """Jitted single step for the given (static) config."""
    return jax.jit(functools.partial(protocol_step, cfg))


# ---------------------------------------------------------------------------
# Device-resident data phase
# ---------------------------------------------------------------------------
def device_data_grads_fn(n: int, batch_fn: Callable, grad_fn: Callable,
                         label_flip: bool = False):
    """Build a scan-compatible ``grads_fn`` whose DATA PHASE runs inside the
    step function: per-peer public-seed batches are generated ON DEVICE
    (vmapped over peers), so a scanned run moves zero batch bytes host->
    device per step.

    batch_fn(peer, step, flipped) -> batch pytree — pure and traceable in
    (peer, step) (e.g. ``TokenPipeline.device_batch`` or
    ``classification_batch`` over ``peer_key``); the public-seed property
    means a validator recomputing peer i's batch gets the same bits on any
    path. grad_fn(params, batch) -> (d,) flat gradient.

    Returns grads_fn(params, t, flips) -> (G, honest_G), the signature
    :func:`scan_protocol` consumes. When ``label_flip``, flipped rows carry
    the flipped-label gradient in G while honest_G keeps the recompute
    (exactly what a validator obtains from the public seed).
    """

    def per_peer(params, i, t, flip):
        g_honest = grad_fn(params, batch_fn(i, t, False))
        if label_flip:
            g = jnp.where(flip, grad_fn(params, batch_fn(i, t, True)),
                          g_honest)
        else:
            g = g_honest
        return g, g_honest

    def grads_fn(params, t, flips):
        return jax.vmap(lambda i, f: per_peer(params, i, t, f))(
            jnp.arange(n), flips
        )

    return grads_fn


# ---------------------------------------------------------------------------
# Scanned multi-step runner
# ---------------------------------------------------------------------------
def scan_protocol(cfg: EngineConfig, state: ProtocolState, byz_mask, params,
                  grads_fn: Callable, n_steps: int, update_fn=None):
    """Run ``n_steps`` protocol rounds under one ``lax.scan`` (no host sync).

    grads_fn(params, t, flip_mask) -> (G, honest_G): pure per-step gradient
    computation over ALL n peers (banned rows are masked internally). Build
    it with :func:`device_data_grads_fn` to fold batch generation into the
    scan (the fully device-resident loop: data -> grads -> attack ->
    butterfly -> verify -> ban, one compiled program, zero per-step host
    traffic). update_fn(params, g_hat, t) -> params: optional optimizer
    inner step. Returns (final_state, final_params, stacked StepOutputs).
    """
    byz = jnp.asarray(byz_mask) > 0

    def body(carry, _):
        st, p = carry
        flips = flip_mask(cfg, st, byz)
        G, honest_G = grads_fn(p, st.step, flips)
        st, out = protocol_step(cfg, st, byz, G, honest_G)
        if update_fn is not None:
            p = update_fn(p, out.g_hat, st.step - 1)
        return (st, p), out

    (state, params), outs = jax.lax.scan(
        body, (state, params), None, length=n_steps
    )
    return state, params, outs


def make_scan_runner(cfg: EngineConfig, grads_fn, n_steps: int,
                     update_fn=None):
    """Jitted closure over scan_protocol: fn(state, byz_mask, params)."""
    return jax.jit(
        lambda state, byz_mask, params: scan_protocol(
            cfg, state, byz_mask, params, grads_fn, n_steps, update_fn
        )
    )


# ---------------------------------------------------------------------------
# Static-analysis hooks (tools.analysis / btard-lint)
# ---------------------------------------------------------------------------
def abstract_state(cfg: EngineConfig) -> ProtocolState:
    """:class:`ProtocolState` as a pytree of ``ShapeDtypeStruct`` leaves —
    the abstract scan carry btard-lint traces the step with (no arrays are
    materialized, no devices are touched)."""
    return jax.eval_shape(lambda: init_state(cfg))


def traceable_phases(cfg: EngineConfig) -> dict:
    """name -> (fn, abstract_args) for every phase this config exercises,
    with argument avals wired exactly as :func:`protocol_step` passes them
    (intermediate shapes derived via ``jax.eval_shape`` chaining, never
    hand-written). btard-lint traces each entry with ``jax.make_jaxpr``
    and asserts purity — no host callbacks, no effects, no PRNG outside
    the :func:`_phase_key` fold-in chain — so a violation is pinned to the
    phase that introduced it rather than to the fused step."""
    n, d = cfg.n, cfg.d
    state = abstract_state(cfg)
    aval = jax.ShapeDtypeStruct
    G = aval((n, d), jnp.float32)
    byz = aval((n,), jnp.bool_)
    weights = aval((n,), jnp.float32)
    seed = aval((), jnp.int32)
    spec = cfg.agg_spec()

    phases = {
        "phase_membership": (
            functools.partial(phase_membership, cfg), (state,)),
        "phase_attack": (
            functools.partial(phase_attack, cfg), (state, G, G, byz)),
        "phase_mprng": (
            functools.partial(phase_mprng, cfg), (state, byz)),
    }

    if spec.verifiable and cfg.hierarchical:
        samp_mask = aval((n,), jnp.bool_) if cfg.audit_k is not None else None
        if comp_mod.is_wrapped(spec):
            codec = comp_mod.codec_of(spec)
            gs = n // cfg.groups
            G_cmp = jax.eval_shape(
                lambda g: comp_mod.wire_grads(g, codec, gs), G)
        else:
            G_cmp = G
        phases["phase_hier"] = (
            functools.partial(phase_hier, cfg),
            (state, byz, weights, seed, G, G_cmp, G_cmp, samp_mask, byz))
        return phases

    samp_idx = None
    if spec.verifiable and cfg.audit_k is not None:
        samp_idx, _ = jax.eval_shape(
            lambda s: hier_mod.sample_audit_cells(
                _phase_key(s, 6), s.step, s.col_checked,
                cfg.m_validators, cfg.audit_k, cfg.n), state)
    agg_fn = functools.partial(phase_aggregation, cfg)
    phases["phase_aggregation"] = (
        agg_fn, (state, G, weights, seed, samp_idx))
    if not spec.verifiable:
        # mean/median/krum baselines: no tables, verify/accuse degrade to
        # no-ops in protocol_step, so aggregation is the last traced phase
        return phases

    agg, parts, z, s_tbl, norm_tbl, _ = jax.eval_shape(
        agg_fn, state, G, weights, seed, samp_idx)
    att_fn = functools.partial(phase_aggregator_attack, cfg)
    phases["phase_aggregator_attack"] = (
        att_fn, (state, agg, parts, z, byz, weights, samp_idx))
    if s_tbl is None:  # aggregator-attack configs compute tables post-shift
        _, _, _, s_tbl, norm_tbl = jax.eval_shape(
            att_fn, state, agg, parts, z, byz, weights, samp_idx)
    corrupt = aval((cfg.n_parts,), jnp.bool_)
    active = aval((n,), jnp.float32)
    phases["phase_misreport"] = (
        functools.partial(phase_misreport, cfg),
        (s_tbl, corrupt, byz, active, weights))
    if comp_mod.is_wrapped(spec):
        G_cmp = jax.eval_shape(
            lambda g: comp_mod.wire_grads(
                g, comp_mod.codec_of(spec), cfg.n_parts), G)
    else:
        G_cmp = G
    ver_fn = functools.partial(phase_verify, cfg)
    ver_args = (state, G_cmp, G_cmp, agg, agg, parts, s_tbl, s_tbl,
                norm_tbl, norm_tbl, byz, weights)
    phases["phase_verify"] = (ver_fn, ver_args)
    accuse, sys_accuse, mismatch_s, _, _, _ = jax.eval_shape(
        ver_fn, *ver_args)
    phases["phase_accuse_ban"] = (
        functools.partial(phase_accuse_ban, cfg),
        (state, accuse, sys_accuse, mismatch_s, byz, G_cmp, G_cmp,
         agg, agg, s_tbl, s_tbl, norm_tbl, norm_tbl))
    return phases
