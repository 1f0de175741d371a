"""The paper's attack zoo (§4.1): what Byzantine peers send instead of
their honest gradients.

All gradient attacks transform the stacked (n, d) gradient matrix given the
Byzantine mask. LABEL FLIP is applied at gradient-computation time (it needs
the loss), so the trainer handles it via ``needs_flipped_labels``.

Two call surfaces share the math:

* ``GRADIENT_ATTACKS`` — the legacy name -> fn dict (host loops pick a fn
  once, outside jit);
* the **registry** (``ATTACK_NAMES`` / ``attack_index`` / ``apply_attack``)
  — every attack as a statically-shaped pure function of the SAME signature
  ``(grads, byz_mask, key, lam, delayed, hon_mask)``, selectable by integer
  index via ``lax.switch``, so the attack choice composes under jit/scan
  (the ProtocolState engine threads the index through ``lax.scan`` without
  retracing per attack).

``hon_mask`` marks the rows whose statistics collusion attacks (IPM, ALIE)
may use — the engine passes ``active & ~byzantine`` so banned peers drop out
of the honest mean/variance exactly as they do in the host protocol, where
banned rows never enter the stacked matrix at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri


def _hon(byz_mask, hon_mask):
    return ~byz_mask if hon_mask is None else hon_mask


def sign_flip(grads, byz_mask, *, lam=1000.0, **_):
    """Each attacker sends -lam * its true gradient (paper amplifies by 1000)."""
    return jnp.where(byz_mask[:, None], -lam * grads, grads)


def random_direction(grads, byz_mask, *, key, lam=1000.0, hon_mask=None, **_):
    """All attackers send a large common random vector."""
    v = jax.random.normal(key, (grads.shape[1],), grads.dtype)
    v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)
    scale = lam * jnp.linalg.norm(grads, axis=1).mean()
    return jnp.where(byz_mask[:, None], (scale * v)[None, :], grads)


def delayed_gradient(grads, byz_mask, *, delayed, **_):
    """Attackers send their real gradients delayed by D steps (trainer keeps
    the history buffer and passes the delayed rows)."""
    return jnp.where(byz_mask[:, None], delayed, grads)


def ipm(grads, byz_mask, *, epsilon=0.6, hon_mask=None, **_):
    """Inner-product manipulation (Xie et al. 2020): attackers send
    -epsilon * mean(honest gradients)."""
    hon = _hon(byz_mask, hon_mask)
    denom = jnp.maximum(hon.sum(), 1)
    mu = (grads * hon[:, None]).sum(0) / denom
    return jnp.where(byz_mask[:, None], (-epsilon * mu)[None, :], grads)


def alie(grads, byz_mask, *, hon_mask=None, **_):
    """A Little Is Enough (Baruch et al. 2019): collude to shift the
    coordinate-wise statistics while staying inside the population variance.

    z_max = Phi^{-1}((n - b - s) / (n - b)),  s = floor(n/2) + 1 - b.
    Attackers send mu - z_max * sigma (coordinate-wise over honest peers).
    """
    n = grads.shape[0]
    b = byz_mask.sum()
    hon = _hon(byz_mask, hon_mask)
    denom = jnp.maximum(hon.sum(), 1)
    mu = (grads * hon[:, None]).sum(0) / denom
    var = ((grads - mu[None]) ** 2 * hon[:, None]).sum(0) / jnp.maximum(denom - 1, 1)
    sigma = jnp.sqrt(var)
    s = jnp.floor_divide(n, 2) + 1 - b
    q = jnp.clip((n - b - s) / jnp.maximum(n - b, 1), 1e-4, 1 - 1e-4)
    z_max = ndtri(q.astype(jnp.float64) if False else q.astype(jnp.float32))
    mal = mu - z_max * sigma
    return jnp.where(byz_mask[:, None], mal[None, :], grads)


def label_flip(grads, byz_mask, **_):
    """Marker: handled at gradient computation (loss with flipped labels)."""
    return grads


GRADIENT_ATTACKS = {
    "none": lambda g, m, **kw: g,
    "sign_flip": sign_flip,
    "random_direction": random_direction,
    "label_flip": label_flip,
    "delayed_gradient": delayed_gradient,
    "ipm_01": lambda g, m, **kw: ipm(g, m, epsilon=0.1, hon_mask=kw.get("hon_mask")),
    "ipm_06": lambda g, m, **kw: ipm(g, m, epsilon=0.6, hon_mask=kw.get("hon_mask")),
    "alie": alie,
}

NEEDS_FLIPPED_LABELS = {"label_flip"}
NEEDS_DELAY_BUFFER = {"delayed_gradient"}


# ---------------------------------------------------------------------------
# Jit-composable registry: one uniform statically-shaped signature per
# attack, dispatched by integer index (lax.switch) inside the engine.
# ---------------------------------------------------------------------------
ATTACK_NAMES = (
    "none",
    "sign_flip",
    "random_direction",
    "label_flip",
    "delayed_gradient",
    "ipm_01",
    "ipm_06",
    "alie",
)
ATTACK_INDEX = {name: i for i, name in enumerate(ATTACK_NAMES)}


def attack_index(kind: str) -> int:
    """Registry index for an attack name (raises KeyError on unknown)."""
    return ATTACK_INDEX[kind]


def rejoin_under_new_key(slot, leave_step, rejoin_step, identity=None):
    """The churn adversary: a (typically already banned) peer vacates its
    slot and rejoins it, continuing whatever gradient attack its slot's
    ``byz_mask`` entry encodes. ``identity=None`` is the NEW-KEY variant —
    ``engine.encode_events`` mints a fresh identity, so the ban ledger does
    not refuse it at admission and the probation spot-check (core.sybil)
    must catch it; pass the original identity for the SAME-KEY variant,
    refused directly from the identity ban ledger. Returns an event
    schedule for ``EngineConfig``/``init_state`` (or ``--churn`` via the
    equivalent ``leave@S:P,join@S:P`` string)."""
    join = ((rejoin_step, "join", slot) if identity is None
            else (rejoin_step, "join", slot, identity))
    return [(leave_step, "leave", slot), join]


def _uniform(fn, **fixed):
    def wrapped(grads, byz_mask, key, lam, delayed, hon_mask):
        return fn(
            grads, byz_mask,
            key=key, lam=lam, delayed=delayed, hon_mask=hon_mask, **fixed,
        )

    return wrapped


_REGISTRY = (
    _uniform(lambda g, m, **_: g),  # none
    _uniform(sign_flip),
    _uniform(random_direction),
    _uniform(label_flip),
    _uniform(delayed_gradient),
    _uniform(ipm, epsilon=0.1),
    _uniform(ipm, epsilon=0.6),
    _uniform(alie),
)


def apply_attack(idx, grads, byz_mask, *, key, lam=1000.0, delayed=None,
                 hon_mask=None):
    """Apply registry attack ``idx`` (int or traced int32) to the stacked
    gradients. All branches share static shapes, so a traced ``idx`` stays
    inside the compiled graph (no host dispatch, scan-safe).

    byz_mask: rows the attack REPLACES (the engine passes active & byz).
    hon_mask: rows collusion statistics may read (active & ~byz).
    delayed:  (n, d) rows for delayed_gradient; zeros otherwise.
    """
    if delayed is None:
        delayed = jnp.zeros_like(grads)
    lam = jnp.asarray(lam, grads.dtype)
    if isinstance(idx, int):  # static choice: no switch, no unused operands
        return _REGISTRY[idx](grads, byz_mask, key, lam, delayed, hon_mask)
    return jax.lax.switch(
        jnp.asarray(idx, jnp.int32),
        _REGISTRY,
        grads, byz_mask, key, lam, delayed, hon_mask,
    )


# ---------------------------------------------------------------------------
# Aggregator-side attacks (a Byzantine peer aggregating a partition lies)
# ---------------------------------------------------------------------------
def aggregator_shift(agg_part, key, scale):
    """Malicious aggregator adds a bounded random shift to its partition
    (bounded because Verification 3 / Delta_max votes catch large ones)."""
    noise = jax.random.normal(key, agg_part.shape, agg_part.dtype)
    noise = noise / jnp.maximum(jnp.linalg.norm(noise), 1e-30)
    return agg_part + scale * noise


def aggregator_shift_all(agg, corrupt_mask, key, scale):
    """Vectorized aggregator attack over the stacked partitions: rows of
    ``agg`` (n_parts, part) where ``corrupt_mask`` is set receive a unit
    random shift scaled by ``scale`` (one independent direction per
    partition). Pure + statically shaped for the jit/scan engine."""
    noise = jax.random.normal(key, agg.shape, jnp.float32)
    noise = noise / jnp.maximum(
        jnp.linalg.norm(noise, axis=1, keepdims=True), 1e-30
    )
    return jnp.where(corrupt_mask[:, None], agg + scale * noise, agg)
