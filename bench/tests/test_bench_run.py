"""One run of a small cell on the CPU, through the harness's own entry."""
import json
import os
import subprocess
import sys

import pytest

from bench import catalog
from bench.tests import bench_cells

REPO = bench_cells.REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_cells.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["albert1", "mamba1"])
def test_result_line(root, cell):
    res = bench_cells.run(root, cell)
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert {"metrics", "device"} <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["attempted"] % 2 == 0
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s", "peak_hbm_gib"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    assert res["device"]["platform"] == "cpu"  # named, never passed off as a chip
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "update_gap", "bans",
                                  "checksum_accused"}
    assert res["checks"]["checksum_accused"]["value"] == 0
    json.dumps(res)


def test_new_files_are_found_by_name(root):
    """A configuration, a traffic mix, a cell's limits and a per-layer
    metric added as files, with entries in BENCHMARK.json, and nothing
    else."""
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny-albert-large.json").read_text())
    cfg.update(name="tiny-albert-wide", overrides={**cfg["overrides"], "d_ff": 192})
    cfg["model"]["d_ff"] = 192
    (b / "configs" / "tiny-albert-wide.json").write_text(json.dumps(cfg))
    tr = json.loads((b / "traffic" / "tiny1.json").read_text())
    (b / "traffic" / "tiny1-s16.json").write_text(json.dumps(dict(tr, name="tiny1-s16", seq=16)))
    (b / "limits" / "wide1.json").write_text((b / "limits" / "albert1.json").read_text())
    (b / "metrics" / "steps_seen.py").write_text(
        '"""Steps in the traced window."""\n\n\ndef read(ctx):\n    return ctx["steps"]\n')
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-albert-wide", "source": "x", "reduced": [],
                          "file": "bench/configs/tiny-albert-wide.json", "why": "test"})
    bm["workloads"].append({"name": "wide1", "config": "tiny-albert-wide",
                            "traffic": "tiny1-s16", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                            "source": "host_clock", "layer": "test", "moves": "tokens_per_s",
                            "workloads": ["wide1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    assert catalog.reader("steps_seen", b)({"steps": 6}) == 6
    res = bench_cells.run(root, "wide1", traced=True)
    assert res["correct"] is True
    assert res["metrics"]["steps_seen"] == {"value": res["attempted"], "unit": "steps"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_command_refuses_a_machine_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "albert-large.btard.1chip",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_peak_counts_the_reserved_temporaries():
    """memory_stats() of a v5e after a chunk of albert-large.btard.1chip: the
    step's temporaries sit in ``peak_bytes_reserved``, not in use."""
    from bench import harness

    stats = {"bytes_in_use": 795096576, "peak_bytes_in_use": 795097088,
             "bytes_reserved": 11938299904, "peak_bytes_reserved": 11938299904}
    assert harness._peak_bytes(stats) == 795097088 + 11938299904
    assert harness._peak_bytes({"peak_bytes_in_use": 5}) == 5
    assert harness._peak_bytes(None) is None
