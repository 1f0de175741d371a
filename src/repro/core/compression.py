"""Wire compression for the butterfly all-to-all — quantized payloads with
EXACT verification (``compressed:<verifiable>`` AggregatorSpec wrappers).

Communication efficiency is the paper's pitch, yet the butterfly all-to-all
of Alg. 2 ships every payload as f32: 4 bytes per coordinate where 1-2 do.
The ``compressed:`` wrapper quantizes each (peer, partition) payload before
the exchange:

* ``codec=int8`` — per-partition symmetric scale: one f32 sidecar scalar
  ``scale = max|x| / 127`` per payload, wire value
  ``q = clip(round(x / scale), -127, 127)`` as int8 (≈4× fewer wire bytes);
* ``codec=bf16`` — dtype truncation, no sidecar (scale ≡ 1; ≈2×).

The soundness problem compression creates is ROUNDING vs the accuse/ban
protocol: if the sender digests its f32 gradient but the verifier digests
what arrived on the wire, every honest peer is eventually accused over
rounding error. The wrapper's contract dissolves this: **every Alg. 6
quantity — the aggregate v_j, the digests s[i,j] / norm[i,j], and the V2
zero-sum checksum where it applies — is computed over the dequantized-from-
wire values**, never the raw gradients. Dequantization
(``q.astype(f32) * scale``) is a pure deterministic function of the wire
bits, so owner, sender and validator recompute bit-identical digests from
the same payload; honest rows can NEVER trip a commitment or table check
(zero honest accusations is structural, not a tolerance). A cheater's
perturbation either survives quantization — then its wire row, and hence
its recomputed digest pair, differs and the existing verify/accuse/ban
phases fire unchanged — or it vanishes below the quantization step, in
which case it also never entered the aggregate: the wire representation IS
the protocol-visible contribution.

V2 (`Σ_i w_i s_i^j ≈ 0`) survives compression for the same reason it exists
at all (core.verification): the identity is over whatever values the
aggregation consumed. Since the aggregate is computed FROM the wire values,
linear digests over wire values still telescope — exactly for
``compressed:verified:mean``, to fixed-point tolerance for
``compressed:butterfly_clip``; :func:`verification.has_zero_checksum`
therefore answers for the inner spec.

Layering (mirrors ``verified:``): the wrapper registers
``compressed:<name>`` for every verifiable spec; digest/aggregation
dispatch lives in :func:`compressed_aggregate` (called from
``verification.spec_aggregate``); the int8-resident fused Pallas kernels
(dequantize+clip+digest / dequantize+mean+digest, kernels/centered_clip.py)
keep the HBM pass count at n_iters + 2 over 1-byte data; the distributed
all_to_all + scale-sidecar exchange is ``launch.steps``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import aggregators as agg_mod
from repro.core import butterfly as bf

PREFIX = "compressed:"
DEFAULT_CODEC = "int8"
CODECS = ("int8", "bf16")
# wire bytes per coordinate (f32 baseline: 4)
CODEC_BYTES = {"int8": 1, "bf16": 2}


def _check_codec(codec: str) -> str:
    if codec not in CODECS:
        raise ValueError(
            f"unknown wire codec {codec!r} (supported: {', '.join(CODECS)})"
        )
    return codec


# ---------------------------------------------------------------------------
# The codecs: quantize / dequantize over the LAST axis
# ---------------------------------------------------------------------------
def quantize(x, codec: str):
    """Project ``x`` (..., part) onto its wire representation.

    Returns ``(wire, scales)`` with ``scales`` of shape ``x.shape[:-1]``
    (one f32 sidecar scalar per payload — the per-partition symmetric
    scale for int8, identically 1 for bf16 so one dequantize serves both).
    Deterministic: same input bits -> same wire bits on every peer, the
    property the exact-verification contract rests on. All-zero payloads
    quantize to scale 0 / wire 0 and dequantize to exact zeros.
    """
    _check_codec(codec)
    x = jnp.asarray(x, jnp.float32)
    if codec == "bf16":
        return x.astype(jnp.bfloat16), jnp.ones(x.shape[:-1], jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    # a scale below the smallest normal f32 would be flushed to zero (CPU
    # and TPU flush denormals) and zero the whole payload; the smallest
    # normal keeps it within half a step
    tiny = jnp.finfo(jnp.float32).tiny
    scale = jnp.where(amax > 0.0, jnp.maximum(amax / 127.0, tiny), 0.0)
    scale = scale.astype(jnp.float32)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize(wire, scales):
    """Wire bits -> the f32 values EVERY digest is computed over.

    One formula for both codecs (bf16 ships scale ≡ 1): upcast then one
    f32 multiply — the same two ops the fused Pallas kernels apply
    in-register, so the kernel and jnp paths see bit-identical values.
    """
    return wire.astype(jnp.float32) * scales[..., None]


def roundtrip(x, codec: str):
    """quantize∘dequantize — the wire projection of ``x`` (f32, same shape)."""
    return dequantize(*quantize(x, codec))


def wire_grads(grads, codec: str, n_parts: int):
    """Project stacked gradients (n, d) through the per-(peer, partition)
    wire codec — what the engine's commitment comparisons and the generic
    aggregation path consume. The butterfly layout fixes the payload
    boundaries: peer i's contribution to partition j is one payload with
    its own sidecar scale (padding coordinates are zero and never raise a
    payload's amax)."""
    n, d = grads.shape
    parts = bf.split_parts(grads, n_parts)  # (n, n_parts, part)
    wire = roundtrip(jnp.swapaxes(parts, 0, 1), codec)
    return jnp.swapaxes(wire, 0, 1).reshape(n, -1)[:, :d]


# ---------------------------------------------------------------------------
# Spec naming: compressed:<verifiable> wrappers
# ---------------------------------------------------------------------------
def is_wrapped(spec_or_name) -> bool:
    """True for ``compressed:<base>`` wrapper specs/names."""
    name = (
        spec_or_name
        if isinstance(spec_or_name, str)
        else agg_mod.resolve_spec(spec_or_name).name
    )
    return name.startswith(PREFIX)


def inner_spec(spec) -> "agg_mod.AggregatorSpec":
    """The wrapped verifiable spec (same params, ``codec`` stripped)."""
    spec = agg_mod.resolve_spec(spec)
    if not is_wrapped(spec):
        raise ValueError(f"not a {PREFIX}* wrapped spec: {spec.name!r}")
    params = tuple((k, v) for k, v in spec.params if k != "codec")
    return agg_mod.AggregatorSpec(spec.name[len(PREFIX):], params)


def codec_of(spec) -> str:
    return _check_codec(agg_mod.resolve_spec(spec).get("codec", DEFAULT_CODEC))


def compressed(spec, codec: str | None = None) -> "agg_mod.AggregatorSpec":
    """Registry combinator: wire-compress a verifiable spec's butterfly
    payloads.

    * already-compressed specs come back unchanged (codec overridden when
      given);
    * verifiable specs (butterfly_clip, verified:*) map to
      ``compressed:<name>`` with the same params plus ``codec``;
    * non-verifiable coordinatewise specs are lifted through ``verified:``
      first — ``compressed(mean)`` is ``compressed:verified:mean`` (wire
      compression rides the butterfly exchange, which is exactly the
      verifiable topology);
    * full-vector specs (krum, geometric_median, centered_clip) raise, as
      for ``verified:``.
    """
    if codec is not None:
        _check_codec(codec)
    spec = agg_mod.resolve_spec(spec)
    if is_wrapped(spec):
        return spec if codec is None else spec.override(codec=codec)
    if not spec.verifiable:
        from repro.core import verification as vf

        spec = vf.verified(spec)
    params = dict(spec.params)
    if codec is not None:
        params["codec"] = codec
    wrapped = agg_mod.AggregatorSpec(
        PREFIX + spec.name, tuple(sorted(params.items()))
    )
    wrapped.definition  # eager validation (wrapper must be registered)
    return wrapped


def parse_spec_text(text: str) -> "agg_mod.AggregatorSpec":
    """Parse the tail of ``compressed:INNER[:k=v,...]`` (the
    ``AggregatorSpec.parse`` hook). The trailing segment is a param list
    iff it contains ``=``; ``codec`` binds to the wrapper, every other
    param to the inner spec — so ``compressed:verified:mean:codec=bf16``
    and ``compressed:butterfly_clip:n_iters=20,codec=bf16`` both parse."""
    head, sep, tail = text.strip().rpartition(":")
    if not (sep and "=" in tail):
        return compressed(agg_mod.AggregatorSpec.parse(text))
    params = {}
    for item in tail.split(","):
        k, s2, v = item.partition("=")
        if not s2:
            raise ValueError(
                f"bad aggregator param {item!r} in {PREFIX}{text!r} "
                "(expected k=v)"
            )
        params[k.strip()] = agg_mod._coerce(v.strip())
    codec = params.pop("codec", None)
    inner = agg_mod.AggregatorSpec.parse(head)
    if params:
        inner = inner.override(**params)
    return compressed(inner, codec=codec)


# ---------------------------------------------------------------------------
# The verifiable aggregation contract over wire values
# ---------------------------------------------------------------------------
def compressed_aggregate(spec, grads, z=None, weights=None, v0=None,
                         use_pallas: bool = False):
    """``verification.spec_aggregate`` for a compressed spec: quantize the
    butterfly payloads, then run the INNER spec's aggregation + digests over
    the dequantized-from-wire values.

    Returns the uniform (agg, parts, s, norms, iters) contract; ``parts``
    are the WIRE values (what every peer actually received), so downstream
    table recomputes (``spec_tables``) and checksum tolerances see the same
    representation the digests were built from.

    With ``use_pallas`` the wire payloads stay in their 1-2 byte dtype in
    HBM: the fused dequantize+clip+digest kernel (butterfly_clip, fixed
    budget) / dequantize+mean+digest kernel (verified:mean) read int8/bf16
    and dequantize in-register — n_iters + 2 (resp. 1) HBM passes over
    quarter-width data. Every other inner spec materializes the f32 wire
    values once and delegates.
    """
    from repro.core import verification as vf

    spec = agg_mod.resolve_spec(spec)
    inner = inner_spec(spec)
    codec = codec_of(spec)
    n, d = grads.shape

    if use_pallas and z is not None:
        stacked = jnp.swapaxes(bf.split_parts(grads, n), 0, 1)
        q, scales = quantize(stacked, codec)  # (n_parts, n, part), (n_parts, n)
        if inner.name == "butterfly_clip":
            p = inner.param_dict()
            if p["adaptive_tol"] is None:
                from repro.kernels.ops import butterfly_clip_fused_dequant_op

                if not p.get("warm_start"):
                    v0 = None
                agg, s, norms = butterfly_clip_fused_dequant_op(
                    q, scales, p["tau"], z, weights, n_iters=p["n_iters"],
                    v0=v0,
                )
                parts = jnp.swapaxes(dequantize(q, scales), 0, 1)
                return agg, parts, s, norms, jnp.asarray(
                    p["n_iters"], jnp.int32
                )
        elif vf.base_spec(inner).name == "mean":
            from repro.kernels.ops import mean_digest_fused_dequant_op

            agg, s, norms = mean_digest_fused_dequant_op(
                q, scales, z, weights
            )
            parts = jnp.swapaxes(dequantize(q, scales), 0, 1)
            return agg, parts, s, norms, jnp.asarray(1, jnp.int32)

    # generic path: materialize the f32 wire values once, delegate to the
    # inner spec (identical digests — dequantize is one deterministic
    # formula everywhere)
    return vf.spec_aggregate(
        inner, wire_grads(grads, codec, n), z=z, weights=weights, v0=v0,
        use_pallas=use_pallas,
    )


# ---------------------------------------------------------------------------
# Registration: one compressed:<name> wrapper per verifiable spec
# ---------------------------------------------------------------------------
def _make_compressed(base_def: "agg_mod.AggregatorDef"):
    def make(n, d, use_pallas, codec=DEFAULT_CODEC, **params):
        _check_codec(codec)
        base_fn = base_def.make(n, d, use_pallas, **params)

        def fn(xs, weights=None, v0=None, key=None):
            return base_fn(wire_grads(xs, codec, n), weights, v0, key)

        return fn

    return make


def register_compressed_wrappers():
    """Register ``compressed:<name>`` for every VERIFIABLE spec in the
    registry (the wire exchange being compressed is the butterfly
    all-to-all, which only verifiable specs ride). Declared params are the
    inner spec's plus ``codec``; capability flags are inherited — the
    wrapper changes the wire representation, not the aggregation contract.
    The flat maker projects through the codec then runs the base fn; the
    verified path with tables is :func:`compressed_aggregate`. Idempotent.
    Runs after ``verification.register_verified_wrappers`` (import chain:
    aggregators -> verification -> this module), so the verified:* wrappers
    are always in the registry by the time this loop sees it."""
    for name, base_def in list(agg_mod.REGISTRY.items()):
        if name.startswith(PREFIX) or not base_def.verifiable:
            continue
        wrapped = PREFIX + name
        if wrapped in agg_mod.REGISTRY:
            continue
        agg_mod.register(agg_mod.AggregatorDef(
            wrapped,
            _make_compressed(base_def),
            defaults=base_def.defaults + (("codec", DEFAULT_CODEC),),
            verifiable=True,
            weighted=base_def.weighted,
            warm_startable=base_def.warm_startable,
            adaptive=base_def.adaptive,
            # NOT inherited: the quantization scale of each wire payload is
            # a max over the whole partition, so a coordinate slice
            # quantizes with different scales than the full vector —
            # split/concat is no longer bitwise (btard-lint C5)
            coordinatewise=False,
        ))


register_compressed_wrappers()
