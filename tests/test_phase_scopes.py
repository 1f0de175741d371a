"""The BTARD step's phase scopes, as the program compiled for a TPU names
them.

Each phase of the step (``launch/steps.py``) and each part of the model
(``models/``) runs under a ``jax.named_scope``, which leaves the compiled
instructions as they are and writes the phase into each instruction's
``op_name`` metadata: a device trace finds an operation's phase by it. The
scanned step is compiled at a small size for one chip of a described v5e,
as the benchmark's cell runs it (device-resident data, jnp CenteredClip),
and with the fused Pallas CenteredClip.
"""
import dataclasses
import re

import jax
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import InputShape, get_config
from repro.data import TokenPipeline
from repro.launch.steps import make_btard_scan_train_step
from repro.models import Model
from repro.optim import sgd

TINY = dict(d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
            vocab_size=256, n_repeats=2, max_position=64, tie_embeddings=True)
SCOPES = ("btard.data", "btard.grads", "btard.aggregate/flatten",
          "btard.aggregate/exchange", "btard.aggregate/clip", "btard.aggregate/verify",
          "btard.aggregate/gather", "btard.optimizer", "model.embed",
          "model.attention", "model.mlp", "model.head")
# unscoped instructions that do no work on the step's tensors: the scan's
# loop (its counter, condition, the slices of its inputs and the updates of
# its stacked outputs), copies and buffer allocations ...
BOOKKEEPING = {"while", "add", "compare", "dynamic-slice",
               "dynamic-update-slice", "copy", "copy-start", "copy-done",
               "custom-call", "parameter", "get-tuple-element", "tuple",
               "constant", "bitcast"}
# ... and the few the compiler forms itself, which keep no phase in their
# op_name: one convert and reshape of the whole aggregate, hoisted out of
# the unflatten's per-leaf slices; iotas of the head's gather indices; one
# reduction over the peers in the clip's loop, named by the step alone
MERGED = {"convert", "reshape", "iota", "reduce"}
SCOPED = re.compile(r"(?:^|[/(])(?:btard|model)\.")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def _step(device, use_pallas=False):
    cfg = dataclasses.replace(get_config("albert-large"), **TINY)
    model = Model(cfg)
    devs = [[device]] if device is not None else [[jax.devices()[0]]]
    mesh = Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    step, abstract = make_btard_scan_train_step(
        model, sgd(0.03, momentum=0.9, nesterov=True), mesh,
        InputShape("t", 32, 4, "train"), n_scan_steps=2, tau=2.0,
        clip_iters=3, use_pallas=use_pallas,
        pipeline=TokenPipeline(cfg.vocab_size, 32, 4),
    )
    rep = NamedSharding(mesh, P())
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        abstract)
    return step.lower(*abstract)


def _computations(hlo):
    """{name: [instruction lines]} and the ENTRY computation's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if m:
            cur, comps[m.group(2)] = m.group(2), []
            entry = entry or (m.group(2) if m.group(1) else None)
        elif line.startswith("}"):
            cur = None
        elif cur and " = " in line:
            comps[cur].append(line)
    return comps, entry


def _device_ops(hlo):
    """(opcode, op_name) of the instructions the device runs one by one:
    those of the entry computation and of loop bodies and conditions, not
    those inside fusions or reductions."""
    comps, entry = _computations(hlo)
    seen, todo, out = set(), [entry], []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:body|condition)=%([\w.\-]+)", line)
            rhs = line.split(" = ", 1)[1]
            opcode = re.match(r"(?:\(.*?\)|\S+) ([a-z][\w\-]*)\(", rhs)
            name = OP_NAME.search(line)
            out.append((opcode.group(1) if opcode else rhs,
                        name.group(1) if name else ""))
    return out


@pytest.fixture(scope="module")
def compiled(described_chip):
    return _step(described_chip).compile().as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_every_phase_is_named_in_the_compiled_step(compiled, scope):
    names = OP_NAME.findall(compiled)
    assert any(re.search(rf"(?:^|[/(]){re.escape(scope)}[/)]", n)
               for n in names)


def test_unscoped_instructions_are_bookkeeping(compiled):
    ops = _device_ops(compiled)
    unscoped = {op for op, name in ops if not SCOPED.search(name)}
    assert unscoped <= BOOKKEEPING | MERGED, unscoped - BOOKKEEPING - MERGED
    assert sum(op in MERGED for op, n in ops if not SCOPED.search(n)) <= 8
    # every fusion and dot the device runs is named by its phase
    assert any(op == "fusion" for op, _ in ops)
    assert "fusion" not in unscoped and "convolution" not in unscoped


def test_pallas_kernels_carry_their_names(described_chip):
    """The fused CenteredClip runs under ``btard.aggregate/clip`` and its
    kernel's name: as the TPU's custom call, and in the interpreter, whose
    operations the name scopes."""
    hlo = _step(described_chip, use_pallas=True).compile().as_text()
    calls = [OP_NAME.search(line).group(1) for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(
        re.search(r"btard\.aggregate/clip/.*cc_fused/pallas_call$", c)
        for c in calls), calls
    interp = _step(None, use_pallas=True).compile().as_text()
    assert "tpu_custom_call" not in interp
    assert re.search(r'op_name="[^"]*btard\.aggregate/clip/[^"]*cc_fused/',
                     interp)
