"""From a profiler trace to device times.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a plain
structure (kept small enough to commit one as a test fixture):

  {"devices": {"<device>": [[op, start_ns, dur_ns, kind], ...]},
   "host":    [[span, start_ns, dur_ns], ...]}

with ``op`` the HLO instruction's name, ``kind`` one of ``custom_call`` (a
Pallas kernel: ``custom_call_target="tpu_custom_call"``), ``collective`` or
``other``, and host spans only of the benchmark's own annotations
(``bench.*``). The device's "XLA Ops" line nests: a loop's event spans the
events of its body. ``reduce`` counts busy time as the union of all of them
and time per kind and per operation over the innermost events only; it also
names the idle gaps by what the host was doing in them.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|ragged-all-to-all|send|recv)"
)
PALLAS = 'custom_call_target="tpu_custom_call"'


def op_name(text: str) -> str:
    """'fusion.636' of '%fusion.636 = bf16[16,512]{...} fusion(...)'."""
    if text.startswith("%") and " = " in text:
        return text[1:text.index(" = ")]
    return text


def _kind(text: str) -> str:
    if PALLAS in text:
        return "custom_call"
    if COLLECTIVE.match(op_name(text)):
        return "collective"
    return "other"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append([op_name(e.name), float(e.start_ns),
                                float(e.duration_ns), _kind(e.name)])
            out["devices"][plane.name.split(":", 1)[1]] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["host"].append(
                            [e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _innermost(evs):
    """The events that contain no other event."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    leaf = [True] * len(evs)
    stack = []
    for i in order:
        start = evs[i][1]
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
        stack.append(i)
    return [e for e, is_leaf in zip(evs, leaf) if is_leaf]


def reduce(tr: dict, top: int = 10) -> dict:
    """Per device: busy seconds (union of operation intervals), seconds per
    kind of the innermost operations; over devices: the mean busy time, the
    operations that took most time (mean seconds a device) and the idle
    gaps of the first device by the host span that overlaps each gap most."""
    per_dev, op_time = {}, defaultdict(float)
    first = None
    for dev in sorted(tr["devices"]):
        evs = tr["devices"][dev]
        if not evs:
            continue
        kinds = defaultdict(float)
        for name, start, dur, kind in _innermost(evs):
            kinds[kind] += dur * 1e-9
            op_time[name] += dur * 1e-9
        merged = _union([(s, s + d) for _, s, d, _ in evs])
        per_dev[dev] = {"busy_s": sum(e - s for s, e in merged) * 1e-9,
                        **{k: kinds.get(k, 0.0) for k in
                           ("custom_call", "collective", "other")}}
        if first is None:
            first = merged
    if not per_dev:
        return {"devices": {}, "busy_s": 0.0, "device_ops": [], "idle_gaps": []}
    n = len(per_dev)
    gaps = defaultdict(float)
    spans = sorted(tr["host"], key=lambda x: x[1])
    for (_, e0), (s1, _) in zip(first, first[1:]):
        best, most = "no bench span", 0.0
        for name, s, d in spans:
            o = min(s + d, s1) - max(s, e0)
            if o > most:
                best, most = name, o
        gaps[best] += (s1 - e0) * 1e-9
    return {
        "devices": per_dev,
        "busy_s": sum(v["busy_s"] for v in per_dev.values()) / n,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda x: -x[1])[:top],
    }
