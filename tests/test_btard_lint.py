"""Negative tests for btard-lint (tools/analysis).

Each test plants one deliberate violation of a protocol invariant and
asserts the *intended* check — and only it — reports a finding. This is
what keeps the linter honest: a rule that never fires on a planted bug is
dead weight, and a rule that fires from the wrong layer would bury real
reports under noise.

Planted violations:

1. host callback inside a protocol phase        -> purity (callback)
2. off-chain PRNG seed (constant-folded key)    -> purity (constant key)
3. upcast of a collective's output, no barrier  -> wire_dtype W1
4. widened operand feeding a collective         -> wire_dtype W2
5. scan-carry shape/treedef drift               -> carry_stability
6. coordinatewise flag on a non-bitwise spec    -> coordinatewise
7. kernel with no ref oracle / manifest entry   -> pallas_completeness
8. illegal TPU block specs (VMEM scalar, lane)  -> pallas_block_specs
"""
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import AbstractMesh, PartitionSpec as P

from tools.analysis import common
from tools.analysis import kernels_check
from tools.analysis.jaxpr_checks import carry_findings_for, purity_findings_for
from tools.analysis.kernels_check import block_spec_findings
from tools.analysis.wire_dtype import wire_findings


def _checks(findings):
    return sorted({f.check for f in findings})


# ---------------------------------------------------------------- purity

def test_planted_host_callback_is_caught():
    def phase(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    args = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    findings = purity_findings_for(phase, args, "planted")
    assert _checks(findings) == ["purity"]
    assert any("callback" in f.message for f in findings)
    # and only purity: the carry of the identity-shaped phase is stable
    assert not carry_findings_for(lambda x: (x,), args[0], (), "planted")


def test_planted_constant_prng_seed_is_caught():
    def phase(x):
        noise = jax.random.normal(jax.random.key(0), x.shape)
        return x + noise

    findings = purity_findings_for(
        phase, (jax.ShapeDtypeStruct((8,), jnp.float32),), "planted")
    assert _checks(findings) == ["purity"]
    assert any("constant" in f.message.lower() or "literal" in
               f.message.lower() or "seed" in f.message.lower()
               for f in findings)


def test_clean_phase_has_no_purity_findings():
    def phase(x, key):
        return x + jax.random.normal(key, x.shape)

    findings = purity_findings_for(
        phase,
        (jax.ShapeDtypeStruct((8,), jnp.float32),
         jax.eval_shape(lambda: jax.random.key(3))),
        "clean")
    assert findings == []


# ------------------------------------------------------------ wire dtype

def _gather_harness(body):
    """Trace body(x) under a 1-axis abstract mesh, x one bf16 shard."""
    mesh = AbstractMesh((8,), ("peers",))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("peers"),),
                       out_specs=P(), check_vma=False)
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((64,), jnp.bfloat16))


def test_planted_unpinned_upcast_is_caught():
    def leaky(x):
        full = jax.lax.all_gather(x, "peers", tiled=True)
        return full.astype(jnp.float32).sum()  # upcast free to hoist

    findings = wire_findings(_gather_harness(leaky), "planted",
                             wire_dtype=jnp.bfloat16)
    assert _checks(findings) == ["wire_dtype"]
    assert any("barrier" in f.message for f in findings)


def test_planted_widened_collective_operand_is_caught():
    def leaky(x):
        return jax.lax.all_gather(  # ships f32: 2x the declared wire
            x.astype(jnp.float32), "peers", tiled=True).sum()

    findings = wire_findings(_gather_harness(leaky), "planted",
                             wire_dtype=jnp.bfloat16)
    assert "wire_dtype" in _checks(findings)


def test_barrier_pinned_upcast_is_clean():
    def pinned(x):
        full = jax.lax.all_gather(x, "peers", tiled=True)
        full = jax.lax.optimization_barrier(full)
        return full.astype(jnp.float32).sum()

    assert wire_findings(_gather_harness(pinned), "clean",
                         wire_dtype=jnp.bfloat16) == []


# ----------------------------------------------------------- scan carry

class _ToyState(typing.NamedTuple):
    step: jax.Array
    acc: jax.Array


_TOY = _ToyState(
    step=jax.ShapeDtypeStruct((), jnp.int32),
    acc=jax.ShapeDtypeStruct((4,), jnp.float32),
)


def test_planted_carry_dtype_drift_is_caught():
    def step(s):
        # acc silently promoted to f64-less world's widest: bf16 -> f32
        # drift planted the other way round: f32 -> bf16
        return _ToyState(s.step + 1, s.acc.astype(jnp.bfloat16)),

    findings = carry_findings_for(step, _TOY, (), "planted")
    assert _checks(findings) == ["carry_stability"]
    assert any("acc" in f.message for f in findings)


def test_planted_carry_treedef_drift_is_caught():
    def step(s):
        return (s.step + 1, s.acc, s.acc),  # extra leaf: treedef drift

    findings = carry_findings_for(step, _TOY, (), "planted")
    assert _checks(findings) == ["carry_stability"]
    assert any("treedef" in f.message for f in findings)


def test_stable_carry_is_clean():
    def step(s):
        return _ToyState(s.step + 1, s.acc * 2.0),

    assert carry_findings_for(step, _TOY, (), "clean") == []


# ------------------------------------------------------ capability flags

def test_planted_noncoordinatewise_flag_is_caught():
    from repro.core import aggregators as agg_mod

    def make(n, d, use_pallas=False):
        def fn(xs, weights, v0, key):
            # global-norm coupling: slices do NOT concat bitwise
            return xs.mean(0) / (1.0 + jnp.linalg.norm(xs)), None

        return fn

    name = "lint_probe_global_norm"
    agg_mod.REGISTRY[name] = agg_mod.AggregatorDef(
        name=name, make=make, defaults=(), coordinatewise=True)
    try:
        from tools.analysis.contracts import check_coordinatewise

        res = check_coordinatewise()
        mine = [f for f in res.findings if f.where == name]
        assert mine and _checks(mine) == ["coordinatewise"]
        assert [f for f in res.findings if f.where != name] == []
    finally:
        del agg_mod.REGISTRY[name]


# ------------------------------------------------------------ kernels

def test_planted_unmapped_kernel_is_caught(monkeypatch):
    from repro.kernels import centered_clip as _k

    monkeypatch.setattr(
        _k, "lint_probe_orphan_pallas", lambda *a: None, raising=False)
    findings = kernels_check.completeness_findings()
    mine = [f for f in findings if f.where == "lint_probe_orphan_pallas"]
    assert mine and _checks(mine) == ["pallas_completeness"]
    assert any("KERNEL_MANIFEST" in f.message for f in mine)
    assert [f for f in findings if f.where != "lint_probe_orphan_pallas"] == []


def test_planted_illegal_block_specs_are_caught():
    def bad_kernel(s_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...] * s_ref[0, 0]

    def call(scale, x):
        return pl.pallas_call(
            bad_kernel,
            grid=(2,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda b: (0, 0)),     # VMEM scalar
                pl.BlockSpec((8, 64), lambda b: (0, b)),    # lane 64
            ],
            out_specs=pl.BlockSpec((8, 64), lambda b: (0, b)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,
        )(scale, x)

    closed = jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
        jax.ShapeDtypeStruct((8, 128), jnp.float32),
    )
    findings = block_spec_findings(closed, "planted")
    assert _checks(findings) == ["pallas_block_specs"]
    msgs = " | ".join(f.message for f in findings)
    assert "SMEM" in msgs         # the (1, 1) VMEM scalar
    assert "lane dim 64" in msgs  # the 64-wide lane tiles


# ------------------------------------------------------------- plumbing

def test_cli_registry_is_complete():
    from tools.analysis import check_names

    assert set(check_names()) == {
        "engine_purity", "engine_carry", "wire_dtype",
        "registry_roundtrip", "capability_flags", "coordinatewise",
        "pallas_completeness", "pallas_block_specs",
    }


def test_checkresult_report_shape():
    res = common.CheckResult("probe")
    res.findings.append(common.Finding("probe", "here", "msg"))
    d = res.to_dict()
    assert d["status"] == "fail" and d["findings"][0]["where"] == "here"
    assert not res.ok
