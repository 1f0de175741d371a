"""Per-phase device time of the BTARD step, from its named scopes.

The step program names its phases with ``jax.named_scope``
(``launch/steps.py``, ``models/``): the top-level ``btard.*`` phases, the
``model.*`` scopes inside the gradient phase, and the named parts of the
robust aggregation. A scope reaches the compiled program only as each HLO
instruction's ``op_name`` metadata; a TPU's trace names each operation by
its HLO text alone. So the phases of a trace ``bench/trace.load`` read are
counted against a map ``{instruction: name stack}`` of the compiled step
(``compiled_scopes``), given as the trace's ``scopes`` key:

  {"devices": ..., "host": ..., "scopes": {op: name_stack}}

``phases`` sums the innermost operations' time by phase; ``program_spans``
and ``program_gaps`` read the program's own host annotations
(``btard.host.*``) and name the device's idle gaps by them.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from bench.trace import _innermost, _union

PROGRAM_SPAN_PREFIX = "btard.host."
AGGREGATE = "btard.aggregate"
AGGREGATE_PARTS = ("flatten", "exchange", "clip", "verify", "gather")
DIRECTED = ("btard.grads", "model.")  # split into fwd / bwd / recompute
_WRAPPER = re.compile(r"^(?:[\w.]+\()+(.*?)\)+$")  # jvp(x), transpose(jvp(x))
_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def program_spans(trace_dir: str) -> list:
    """[[span, start_ns, dur_ns], ...] of the program's host annotations
    (``btard.host.*``) in the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_SPAN_PREFIX)]


def scopes_of(hlo_text: str) -> dict:
    """{instruction: name stack} of a compiled module's text, from each
    instruction's ``op_name`` metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2).replace('\\"', '"').replace(
                "\\'", "'")
    return out


def compiled_scopes(step, abstract) -> dict:
    """``scopes_of`` the jitted ``step`` compiled for ``abstract``.

    The persistent compile cache keys a program without its metadata, so
    the executable that ran may be one an earlier build of the program
    compiled, under that build's names. The step's lowering (which names
    the instructions as the run's did) is compiled afresh, past the
    persistent cache and, by a compiler option left at its default, past
    the in-memory one. The compiler gives the instructions the same names
    either way: the metadata does not enter what it builds."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = step.lower(*abstract).compile(
            {"xla_dump_hlo_as_text": False}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return scopes_of(text)


def _unwrap(part: str) -> str:
    """'btard.grads' of 'transpose(jvp(btard.grads))'."""
    m = _WRAPPER.match(part)
    return m.group(1) if m else part


def phase_of(stack: str) -> str:
    """The phase of one operation by its name stack: the deepest ``btard.*``
    or ``model.*`` scope (an aggregation part as ``btard.aggregate/<part>``),
    or ``unscoped``. The gradient phase and the model's scopes are split by
    direction: ``recompute`` (a rematerialised forward inside the backward),
    ``bwd`` (a transposed operation) or ``fwd``."""
    parts = stack.split("/")[:-1]  # the last part names the primitive
    deepest, in_aggregate = None, False
    for part in parts:
        name = _unwrap(part)
        if name.startswith(("btard.", "model.")):
            deepest, in_aggregate = name, name == AGGREGATE
        elif in_aggregate and name in AGGREGATE_PARTS:
            deepest = f"{AGGREGATE}/{name}"
    if deepest is None:
        return "unscoped"
    if deepest.startswith(DIRECTED):
        if "rematted_computation" in parts:
            return deepest + "/recompute"
        if any(p.startswith("transpose(") for p in parts):
            return deepest + "/bwd"
        return deepest + "/fwd"
    return deepest


def phases(tr: dict) -> dict:
    """{phase: seconds a device} of the innermost operations, the mean over
    devices (``phase_of`` each operation's name stack; an operation the
    scopes do not name is ``unscoped``). Empty where no operation runs
    under a ``btard.*`` scope: a program without phase scopes."""
    scopes = tr.get("scopes") or {}
    if not any("btard." in v for v in scopes.values()):
        return {}
    memo, out, n = {}, defaultdict(float), 0
    for evs in tr["devices"].values():
        if not evs:
            continue
        n += 1
        for name, _, dur, _ in _innermost(evs):
            if name not in memo:
                memo[name] = phase_of(scopes[name]) if name in scopes else "unscoped"
            out[memo[name]] += dur * 1e-9
    return {k: v / n for k, v in out.items()} if n else {}


def program_gaps(tr: dict, top: int = 10) -> list:
    """[[span, seconds], ...] of the first device's idle gaps (between the
    union of its operation intervals), each given to the innermost program
    span of ``tr["program"]`` that overlaps it: the overlapping span inside
    the most others, then the one overlapping most."""
    devs = [d for d in sorted(tr["devices"]) if tr["devices"][d]]
    if not devs:
        return []
    busy = _union([(s, s + d) for _, s, d, _ in tr["devices"][devs[0]]])
    spans = tr.get("program", [])
    gaps = defaultdict(float)
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        over = [(n, s, s + d) for n, s, d in spans if min(s + d, s1) > max(s, e0)]
        best = "no program span"
        if over:
            best = max(over, key=lambda x: (
                sum(s <= x[1] and x[2] <= e for _, s, e in over),
                min(x[2], s1) - max(x[1], e0)))[0]
        gaps[best] += (s1 - e0) * 1e-9
    return sorted(([k, v] for k, v in gaps.items()), key=lambda x: -x[1])[:top]
