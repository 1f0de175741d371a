"""The comparison that decides ``correct`` fails what it has to fail.

Each test drives the rest of a run of a small cell, past the look for a
chip, with the timed path broken underneath, and sees ``correct`` come out
false; the float8 control put in the program's place fails as well."""
import os
import subprocess
import sys
import textwrap

import pytest

from bench import catalog, harness, reference
from bench.tests import bench_cells


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_cells.make_root(tmp_path_factory.mktemp("bench"))


def test_sound_step_is_correct(root):
    assert bench_cells.run(root, "albert1", seed=2)["correct"] is True


def test_float8_control_in_the_programs_place(root, monkeypatch):
    bm = catalog.load_benchmark(root)
    cfg = catalog.config(bm, "tiny-albert-large", root)
    tr = catalog.traffic("tiny1", root / "bench")
    seed = 2

    def control(self):
        return reference.follow(cfg, tr, seed, harness.base_step(seed), 4, mode="fp8")

    monkeypatch.setattr(harness.Program, "first_steps", control)
    res = bench_cells.run(root, "albert1", seed=seed)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_step_that_returns_its_state_unchanged(root, monkeypatch):
    import repro.launch.steps as steps

    build = steps.make_btard_scan_train_step

    def unchanged(*a, **k):
        step, abstract = build(*a, **k)

        def frozen(params, opt_state, *rest):
            _, _, metrics, verif, _ = step(params, opt_state, *rest)
            return params, opt_state, metrics, verif, rest[-1]

        return frozen, abstract

    monkeypatch.setattr(steps, "make_btard_scan_train_step", unchanged)
    res = bench_cells.run(root, "albert1", seed=2)
    assert res["correct"] is False
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(root, monkeypatch):
    from repro.models import model as model_mod

    loss_fn = model_mod.Model.loss_fn

    def half(self, params, batch):
        t = batch["tokens"]
        return loss_fn(self, params, {**batch, "tokens": t[: t.shape[0] // 2]})

    monkeypatch.setattr(model_mod.Model, "loss_fn", half)
    res = bench_cells.run(root, "albert1", seed=2)
    assert res["correct"] is False


def test_corrupted_digest_table(root, monkeypatch):
    """An owner's digest row that breaks the zero-sum identity implicates a
    peer, though the step itself is sound."""
    import repro.launch.steps as steps

    emit = steps._emit_tables

    def corrupted(g_vec, d, pad, agg, s_local, *rest, **k):
        return emit(g_vec, d, pad, agg, s_local + 1.0, *rest, **k)

    monkeypatch.setattr(steps, "_emit_tables", corrupted)
    res = bench_cells.run(root, "albert1", seed=2)
    assert res["correct"] is False
    assert res["checks"]["checksum_accused"]["value"] > 0
    assert res["checks"]["grad_gap"]["value"] <= res["checks"]["grad_gap"]["limit"]


EXCHANGE = textwrap.dedent("""
    import sys, json
    from pathlib import Path
    import jax.numpy as jnp
    from bench.tests import bench_cells
    import repro.launch.steps as steps
    root = bench_cells.make_root(Path(sys.argv[1]))
    sound = bench_cells.run(root, "albert4", seed=3)["correct"]
    stage = steps.aggregation_stage

    def local(g_vec, *a, **k):  # every peer keeps its own gradient
        _, verif = stage(g_vec, *a, **k)
        return g_vec.astype(jnp.float32), verif

    steps.aggregation_stage = local
    broken = bench_cells.run(root, "albert4", seed=3)["correct"]
    print(json.dumps({"sound": sound, "broken": broken}))
""")


def test_exchange_between_chips_left_out(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(bench_cells.REPO),
                                           str(bench_cells.REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", EXCHANGE, str(tmp_path)],
                       cwd=bench_cells.REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == '{"sound": true, "broken": false}'
