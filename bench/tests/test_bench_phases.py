"""Per-phase device time from the step's named scopes, and the idle gaps
named by the program's host spans."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import phases, trace

DATA = Path(__file__).resolve().parent / "data"


def test_program_spans_of_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    jax.block_until_ready(f(jnp.ones(4)))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("btard.host.fetch"):
        with jax.profiler.TraceAnnotation("btard.host.checksum"):
            jax.block_until_ready(f(jnp.ones(4)))
    with jax.profiler.TraceAnnotation("bench.boundary"):
        pass
    jax.profiler.stop_trace()
    spans = phases.program_spans(str(tmp_path))
    assert sorted(n for n, _, _ in spans) == ["btard.host.checksum",
                                               "btard.host.fetch"]
    (fs, fd), (cs, cd) = [(s, d) for n, s, d in sorted(spans, reverse=True)]
    assert fs <= cs and cs + cd <= fs + fd  # checksum inside fetch
    with pytest.raises(FileNotFoundError):
        phases.program_spans(str(tmp_path / "none"))


S = "jit(scan_step)/while/body/closed_call/"


@pytest.mark.parametrize("stack,phase", [
    (S + "btard.data/jit(_randint)/threefry2x32", "btard.data"),
    (S + "btard.grads/jvp(model.head)/exp", "model.head/fwd"),
    (S + "btard.grads/model.embed/gather", "model.embed/fwd"),  # primitive
    (S + "btard.grads/transpose(jvp(btard.grads))/jvp()/checkpoint/"
     "rematted_computation/model.head/reduce_max", "model.head/recompute"),
    (S + "transpose(jvp(btard.grads))/while/body/closed_call/checkpoint/"
     "model.mlp/dot_general", "model.mlp/bwd"),
    (S + "transpose(jvp(btard.grads))/add_any", "btard.grads/bwd"),
    (S + "jvp(btard.grads)/add", "btard.grads/fwd"),
    (S + "model.attention/le", "model.attention/fwd"),  # hoisted from the loop
    (S + "btard.aggregate/flatten/concatenate", "btard.aggregate/flatten"),
    (S + "btard.aggregate/clip/while/body/mul", "btard.aggregate/clip"),
    (S + "btard.aggregate/clip/gather", "btard.aggregate/clip"),  # primitive
    (S + "btard.aggregate/clip/jit(centered_clip_fused_op)/cond/branch_0_fun/"
     "cc_fused/pallas_call", "btard.aggregate/clip"),
    (S + "btard.aggregate/verify/gather/all_gather", "btard.aggregate/gather"),
    (S + "btard.aggregate/axis_index", "btard.aggregate"),
    (S + "btard.optimizer/mul", "btard.optimizer"),
    ("jit(scan_step)/while/body/add", "unscoped"),
])
def test_phase_of_a_name_stack(stack, phase):
    assert phases.phase_of(stack) == phase


def test_phases_hand_counted():
    names = {"while.1": "jit(scan_step)/while",
             "fusion.1": S + "btard.data/add",
             "fusion.2": S + "jvp(btard.grads)/model.mlp/dot_general",
             "fusion.3": S + "transpose(jvp(btard.grads))/add_any",
             "fusion.4": S + "transpose(jvp(btard.grads))/checkpoint/"
                         "rematted_computation/model.attention/exp",
             "while.2": S + "btard.aggregate/clip/while",
             "fusion.5": S + "btard.aggregate/clip/while/body/mul",
             "all-gather.6": S + "btard.aggregate/gather/all_gather",
             "fusion.7": S + "btard.optimizer/mul"}
    tr = {
        "devices": {
            "TPU:0": [["while.1", 0, 1000, "other"],  # the scan: holds all
                      ["fusion.1", 0, 100, "other"],
                      ["fusion.2", 100, 200, "other"],
                      ["fusion.3", 300, 50, "other"],
                      ["fusion.4", 350, 50, "other"],
                      ["while.2", 400, 300, "other"],  # holds fusion.5 twice
                      ["fusion.5", 400, 150, "other"],
                      ["fusion.5", 550, 150, "other"],
                      ["all-gather.6", 700, 100, "collective"],
                      ["fusion.7", 800, 100, "other"],
                      ["copy.8", 900, 100, "other"]],  # no scope named
            "TPU:1": [["fusion.2", 0, 400, "other"],
                      ["fusion.7", 400, 200, "other"]],
        },
        "host": [], "scopes": names,
    }
    ph = phases.phases(tr)
    ns = 1e-9 / 2  # the mean over two chips
    assert ph == pytest.approx({
        "btard.data": 100 * ns, "model.mlp/fwd": (200 + 400) * ns,
        "btard.grads/bwd": 50 * ns, "model.attention/recompute": 50 * ns,
        "btard.aggregate/clip": 300 * ns, "btard.aggregate/gather": 100 * ns,
        "btard.optimizer": (100 + 200) * ns, "unscoped": 100 * ns})
    # no btard scope: the phases of a program that names none are not read
    assert phases.phases(dict(tr, scopes={"fusion.1": S + "add"})) == {}
    assert phases.phases({k: v for k, v in tr.items() if k != "scopes"}) == {}


def test_program_gaps_go_to_the_innermost_span():
    tr = {"devices": {"TPU:0": [["fusion.1", 0, 100, "other"],
                                ["fusion.1", 200, 100, "other"],
                                ["fusion.1", 400, 100, "other"],
                                ["fusion.1", 600, 100, "other"]]},
          "host": [],
          "program": [["btard.host.fetch", 100, 250],  # holds the next
                      ["btard.host.checksum", 180, 10],
                      ["btard.host.membership", 390, 5]]}
    # [100,200]: under fetch and, inside it, checksum; [300,400]: fetch
    # overlaps 50 ns of it, membership 5; [500,600]: no program span
    assert dict(phases.program_gaps(tr)) == pytest.approx(
        {"btard.host.checksum": 100e-9, "btard.host.fetch": 100e-9,
         "no program span": 100e-9})
    # the benchmark's own idle gaps do not see the program's spans
    assert dict(trace.reduce(tr)["idle_gaps"]) == pytest.approx(
        {"no bench span": 300e-9})
    assert phases.program_gaps({"devices": {}, "host": []}) == []


def test_scopes_of_compiled_text():
    text = (
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(f)/btard.optimizer/mul" stack_frame_id=2}\n'
        '  ROOT %copy.1 = f32[4]{0} copy(%fusion.3)\n'
        '  %x.2 = f32[] parameter(0), metadata={op_name="params[\\\'w\\\']"}\n')
    assert phases.scopes_of(text) == {"fusion.3": "jit(f)/btard.optimizer/mul",
                                     "x.2": "params['w']"}


def test_compiled_scopes_name_the_phases():
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("btard.grads"):
            x = jnp.sin(x) * 3.0
        with jax.named_scope("btard.optimizer"):
            return x + 1.0

    f = jax.jit(step)
    abstract = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    was = jax.config.jax_enable_compilation_cache
    got = phases.compiled_scopes(f, abstract)
    assert jax.config.jax_enable_compilation_cache == was
    found = {phases.phase_of(v) for v in got.values()}
    assert {"btard.grads/fwd", "btard.optimizer"} <= found


def test_phases_of_a_trace_recorded_on_the_chip():
    """100 ms of albert-large.btard.1chip with the step's phase scopes,
    traced on a TPU v5e, checked against a second count: the ops inside
    which no other op starts (a loop and the first op of its body start
    together: the shorter lies inside), each given to the top-level phase
    whose name its stack holds (a model scope to the gradient phase)."""
    rec = json.loads((DATA / "trace_albert_1chip_phases.json").read_text())
    tr = rec["trace"]
    evs = tr["devices"]["TPU:0"]
    start = np.array([e[1] for e in evs])
    dur = np.array([e[2] for e in evs])
    srt = np.sort(start)
    inside = (np.searchsorted(srt, start + dur, "left")
              - np.searchsorted(srt, start, "left")
              - ((start[:, None] == start[None, :])
                 & (dur[None, :] >= dur[:, None])).sum(1))
    tops = ("btard.data", "btard.grads", "btard.aggregate", "btard.optimizer")
    count = dict.fromkeys(tops + ("unscoped",), 0.0)
    for e, n_in in zip(evs, inside):
        if n_in > 0:
            continue
        stack = tr["scopes"].get(e[0], "")
        top = next((t for t in tops if t in stack), None)
        if top is None and "model." in stack:
            top = "btard.grads"
        count[top or "unscoped"] += e[2] * 1e-9
    ph = phases.phases(tr)
    got = dict.fromkeys(count, 0.0)
    for k, v in ph.items():
        got["btard.grads" if k.startswith("model.") else k.split("/")[0]] += v
    assert got == pytest.approx(count, rel=1e-9, abs=1e-12)
    dev = trace.reduce(tr)["devices"]["TPU:0"]
    assert sum(ph.values()) == pytest.approx(
        dev["other"] + dev["custom_call"] + dev["collective"], rel=1e-9)
    assert {"btard.aggregate/clip", "btard.aggregate/verify", "btard.optimizer",
            "btard.data", "model.attention/fwd", "model.mlp/fwd",
            "model.head/fwd"} <= set(ph)
    assert ph["unscoped"] < 0.05 * sum(ph.values())
    # without the name stacks, the same trace names no phase
    assert phases.phases({k: v for k, v in tr.items() if k != "scopes"}) == {}
