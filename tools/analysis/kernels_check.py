"""btard-lint layer 4: Pallas kernel completeness + TPU block-spec legality.

Every ``*_pallas`` kernel in ``repro.kernels.centered_clip`` must ship with
its full support surface, or the next refactor silently loses coverage:

* **K1 — completeness**: a ``ref.py`` oracle (the jnp ground truth the
  parity tests compare against), a jitted ``ops.py`` wrapper (directly or
  via the public kernel that composes it), and a Mosaic lowering test in
  ``tests/test_pallas_compile.py``. The manifest below is the authoritative
  map; a kernel missing from it — or naming a wrapper/oracle/test that
  does not exist — is a finding.
* **K2 — block-spec legality** via abstract eval (no TPU needed): trace
  each ops wrapper with the canonical shapes and walk every
  ``pallas_call``'s grid mapping. Scalars (all-ones blocks) must live in
  SMEM — a (1, 1) VMEM block is an illegal sub-tile on real TPUs — and
  vector blocks must tile to the dtype's sublane/lane minimums (f32 (8,
  128), bf16 (16, 128), int8 (32, 128)) unless the block spans the full
  array dimension. Exactly the PR 2 bug class, checked statically.
"""
from __future__ import annotations

import inspect
import pathlib
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tools.analysis.common import CheckResult, Finding, iter_eqns

# canonical trace shapes — mirrors tests/test_pallas_compile.py
N, D, PARTS, ITERS = 8, 384, 4, 5
PART = D // PARTS

# kernel -> (ref.py oracle, ops.py wrapper that reaches it); the Mosaic
# lowering test is located by the kernel's own name in
# tests/test_pallas_compile.py (the tests call kernels directly)
KERNEL_MANIFEST = {
    "centered_clip_pallas": ("centered_clip_ref", "centered_clip_op"),
    "butterfly_clip_pallas": ("centered_clip_ref", "butterfly_clip_op"),
    "centered_clip_fused_pallas": (
        "centered_clip_fused_ref", "centered_clip_fused_op"),
    "butterfly_clip_fused_pallas": (
        "centered_clip_fused_ref", "butterfly_clip_fused_op"),
    "butterfly_clip_fused_dequant_pallas": (
        "centered_clip_fused_dequant_ref", "butterfly_clip_fused_dequant_op"),
    "adaptive_clip_step_pallas": (
        "adaptive_step_ref", "butterfly_clip_adaptive_op"),
    "butterfly_clip_adaptive_pallas": (
        "adaptive_step_ref", "butterfly_clip_adaptive_op"),
    "verify_tables_pallas": ("verify_tables_ref", "verify_tables_op"),
    "verify_tables_batched_pallas": (
        "verify_tables_ref", "verify_tables_all_op"),
    "digest_tables_batched_pallas": (
        "digest_tables_ref", "digest_tables_all_op"),
    "digest_tables_rows_pallas": (
        "digest_tables_rows_ref", "digest_tables_rows_op"),
    "mean_digest_fused_pallas": (
        "mean_digest_fused_ref", "mean_digest_fused_op"),
    "mean_digest_fused_dequant_pallas": (
        "mean_digest_fused_dequant_ref", "mean_digest_fused_dequant_op"),
}

# minimum sublane per element size (pallas_guide: f32/i32 (8,128),
# bf16 (16,128), int8/fp8 (32,128))
_MIN_SUBLANE = {4: 8, 2: 16, 1: 32}
_LANE = 128


def _trace_cases():
    """(label, thunk) per ops wrapper, canonical shapes. Thunks return the
    traced callable + abstract args — built lazily so import stays light."""
    from repro.kernels import ops

    f32 = jnp.float32
    xs = jax.ShapeDtypeStruct((N, D), f32)
    vec = jax.ShapeDtypeStruct((D,), f32)
    w = jax.ShapeDtypeStruct((N,), f32)
    parts = jax.ShapeDtypeStruct((PARTS, N, PART), f32)
    pvec = jax.ShapeDtypeStruct((PARTS, PART), f32)
    qs = jax.ShapeDtypeStruct((PARTS, N, PART), jnp.int8)
    scales = jax.ShapeDtypeStruct((PARTS, N), f32)
    rows = jax.ShapeDtypeStruct((2,), jnp.int32)
    return (
        ("centered_clip_op", lambda: jax.make_jaxpr(
            lambda a, b, c: ops.centered_clip_op(
                a, 1.0, b, c, n_iters=ITERS))(xs, w, vec)),
        ("verify_tables_op", lambda: jax.make_jaxpr(
            lambda a, b, c: ops.verify_tables_op(a, b, c, 1.0))(
                xs, vec, vec)),
        ("butterfly_clip_op", lambda: jax.make_jaxpr(
            lambda a, b, c: ops.butterfly_clip_op(
                a, 1.0, b, c, n_iters=ITERS))(parts, w, pvec)),
        ("centered_clip_fused_op", lambda: jax.make_jaxpr(
            lambda a, z, b, c: ops.centered_clip_fused_op(
                a, 1.0, z, b, v0=c, n_iters=ITERS))(xs, vec, w, vec)),
        ("butterfly_clip_fused_op", lambda: jax.make_jaxpr(
            lambda a, z, b, c: ops.butterfly_clip_fused_op(
                a, 1.0, z, b, v0=c, n_iters=ITERS))(parts, pvec, w, pvec)),
        ("butterfly_clip_fused_dequant_op", lambda: jax.make_jaxpr(
            lambda a, s, z, b: ops.butterfly_clip_fused_dequant_op(
                a, s, 1.0, z, b, n_iters=ITERS))(qs, scales, pvec, w)),
        ("butterfly_clip_adaptive_op", lambda: jax.make_jaxpr(
            lambda a, b: ops.butterfly_clip_adaptive_op(
                a, 1.0, 1e-4, b, max_iters=ITERS))(parts, w)),
        ("butterfly_clip_fused_adaptive_op", lambda: jax.make_jaxpr(
            lambda a, z, b: ops.butterfly_clip_fused_adaptive_op(
                a, 1.0, z, 1e-4, b, max_iters=ITERS))(parts, pvec, w)),
        ("verify_tables_all_op", lambda: jax.make_jaxpr(
            lambda a, b, z: ops.verify_tables_all_op(a, b, z, 1.0))(
                parts, pvec, pvec)),
        ("digest_tables_all_op", lambda: jax.make_jaxpr(
            ops.digest_tables_all_op)(parts, pvec, pvec)),
        ("digest_tables_rows_op", lambda: jax.make_jaxpr(
            lambda a, b, z, r: ops.digest_tables_rows_op(
                a, b, z, r, tau=1.0))(parts, pvec, pvec, rows)),
        ("mean_digest_fused_op", lambda: jax.make_jaxpr(
            ops.mean_digest_fused_op)(parts, pvec, w)),
        ("mean_digest_fused_dequant_op", lambda: jax.make_jaxpr(
            ops.mean_digest_fused_dequant_op)(qs, scales, pvec, w)),
    )


def discovered_kernels():
    from repro.kernels import centered_clip as _k

    return tuple(sorted(
        name for name in dir(_k)
        if name.endswith("_pallas") and callable(getattr(_k, name))
        and not name.startswith("_")
    ))


def completeness_findings(repo_root: str | pathlib.Path | None = None):
    """K1 over the discovered kernel set."""
    from repro.kernels import centered_clip as _k
    from repro.kernels import ops, ref

    root = pathlib.Path(repo_root) if repo_root else (
        pathlib.Path(inspect.getfile(_k)).resolve().parents[3])
    test_path = root / "tests" / "test_pallas_compile.py"
    test_src = test_path.read_text() if test_path.exists() else ""
    ops_src = inspect.getsource(ops)
    kernels_src = inspect.getsource(_k)

    findings = []
    for kernel in discovered_kernels():
        entry = KERNEL_MANIFEST.get(kernel)
        if entry is None:
            findings.append(Finding(
                "pallas_completeness", kernel,
                "kernel is not in KERNEL_MANIFEST: declare its ref.py "
                "oracle, ops.py wrapper and lowering test",
            ))
            continue
        oracle, wrapper = entry
        if not hasattr(ref, oracle):
            findings.append(Finding(
                "pallas_completeness", kernel,
                f"declared oracle ref.{oracle} does not exist",
            ))
        if not hasattr(ops, wrapper):
            findings.append(Finding(
                "pallas_completeness", kernel,
                f"declared wrapper ops.{wrapper} does not exist",
            ))
        # the kernel must be reachable from ops: referenced there directly,
        # or called by another kernel in centered_clip.py (composition)
        called_in_ops = f"{kernel}(" in ops_src
        composed = kernels_src.count(f"{kernel}(") > 1  # beyond its def
        if not (called_in_ops or composed):
            findings.append(Finding(
                "pallas_completeness", kernel,
                "kernel is unreachable: no ops.py wrapper calls it and no "
                "other kernel composes it",
            ))
        if kernel not in test_src:
            findings.append(Finding(
                "pallas_completeness", kernel,
                f"no Mosaic lowering test: {test_path.name} never "
                f"references {kernel}",
            ))
    return findings


def block_spec_findings(closed, where: str):
    """K2 over every pallas_call in one traced wrapper."""
    findings = []
    for e in iter_eqns(closed.jaxpr):
        if e.primitive.name != "pallas_call":
            continue
        gm = e.params["grid_mapping"]
        for bm in gm.block_mappings:
            arr = bm.array_aval
            # tiled dims (pl.Blocked); squeezed dims carry no block size
            dims = [s.block_size for s in bm.block_shape
                    if isinstance(s, pl.Blocked)]
            if not dims:
                continue
            space = str(getattr(bm.transformed_block_aval, "memory_space",
                                None) or "")
            origin = f"{where}:{bm.origin}"
            if all(s == 1 for s in dims):
                if "smem" not in space.lower():
                    findings.append(Finding(
                        "pallas_block_specs", origin,
                        f"scalar block {tuple(bm.block_shape)} of "
                        f"{arr.shape}/{arr.dtype} placed in "
                        f"{space or 'VMEM'}: scalars must use "
                        "BlockSpec(memory_space=SMEM) (illegal (1, 1) "
                        "VMEM sub-tile on TPU)",
                    ))
                continue
            if "smem" in space.lower():
                continue  # scalar-prefetch / SMEM arrays have no tiling
            lane = dims[-1]
            if lane % _LANE != 0 and lane != arr.shape[-1]:
                findings.append(Finding(
                    "pallas_block_specs", origin,
                    f"lane dim {lane} of block {tuple(bm.block_shape)} is "
                    f"neither a multiple of {_LANE} nor the full array "
                    f"dim {arr.shape[-1]}",
                ))
            if len(dims) >= 2 and len(arr.shape) >= 2:
                sub = dims[-2]
                want = _MIN_SUBLANE.get(jnp.dtype(arr.dtype).itemsize, 8)
                if sub % want != 0 and sub != arr.shape[-2]:
                    findings.append(Finding(
                        "pallas_block_specs", origin,
                        f"sublane dim {sub} of block "
                        f"{tuple(bm.block_shape)} ({arr.dtype}) is neither "
                        f"a multiple of {want} nor the full array dim "
                        f"{arr.shape[-2]}",
                    ))
    return findings


def check_pallas_completeness() -> CheckResult:
    t0 = time.time()
    res = CheckResult("pallas_completeness")
    res.findings += completeness_findings()
    res.traced = len(discovered_kernels())
    res.seconds = time.time() - t0
    return res


def check_pallas_block_specs() -> CheckResult:
    t0 = time.time()
    res = CheckResult("pallas_block_specs")
    for label, thunk in _trace_cases():
        res.findings += block_spec_findings(thunk(), label)
        res.traced += 1
    res.seconds = time.time() - t0
    return res
