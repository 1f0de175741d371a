"""Small cells for the benchmark's CPU tests.

``make_root(tmp)`` writes a checkout-like tree: a ``BENCHMARK.json`` with
cells of the real configurations cut to a few thousand parameters (every
other number as the real files have it), their traffic mixes at a few
hundred tokens, the real per-layer metric readers, and limits set for these
sizes on the CPU from seeds 1-3: the small ALBERT read at most 5.0e-5 (loss),
2.3e-3 (first gradient) and 4.1e-3 (change), its float8 control at least
1.1e-3, 3.1e-2 and 2.8e-2; the small Mamba-2 at most 2.3e-4, 2.2e-2 and
9.6e-3, its control at least 2.9e-3, 6.4e-2 and 6.7e-2.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"

TINY = {  # small configuration: (the file it is cut from, the cut)
    "tiny-albert-large": ("albert-large-e1024", {
        "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
        "d_ff": 128, "vocab_size": 256, "n_repeats": 2, "max_position": 64}),
    "tiny-mamba2-2.7b-d4": ("mamba2-2.7b-d4", {
        "d_model": 64, "vocab_size": 256, "n_repeats": 2, "ssm_state": 16,
        "ssm_head_dim": 16, "ssm_chunk": 8}),
}
LIMITS = {
    "albert": {"loss_gap": 6e-4, "grad_gap": 6e-3, "update_gap": 8e-3},
    "mamba": {"loss_gap": 1e-3, "grad_gap": 4e-2, "update_gap": 3e-2},
}


def make_root(tmp: Path) -> Path:
    root = Path(tmp)
    b = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (b / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", b / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    bm["configs"], bm["workloads"] = [], []
    for tiny, (name, cut) in TINY.items():
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        cfg.update(name=tiny, overrides={**cfg["overrides"], **cut})
        cfg["model"].update(cut)
        (b / "configs" / f"{tiny}.json").write_text(json.dumps(cfg))
        bm["configs"].append({"name": tiny, "source": cfg["source"],
                              "file": f"bench/configs/{tiny}.json",
                              "reduced": sorted(cut), "why": "CPU test"})
    for peers in (1, 4):
        tr = json.loads((BENCH / "traffic" / "peer1-b128-s512.json").read_text())
        tr.update(name=f"tiny{peers}", mesh=[peers, 1], per_peer_batch=4, seq=32)
        (b / "traffic" / f"tiny{peers}.json").write_text(json.dumps(tr))
    for cell, model, peers in (("albert1", "albert", 1), ("mamba1", "mamba", 1),
                               ("albert4", "albert", 4)):
        cfg = "tiny-albert-large" if model == "albert" else "tiny-mamba2-2.7b-d4"
        bm["workloads"].append({"name": cell, "config": cfg,
                                "traffic": f"tiny{peers}", "chips": peers,
                                "why": "CPU test"})
        (b / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS[model]))
    for m in bm["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def run(root: Path, cell: str, seed: int = 1, seconds: float = 0.3,
        traced: bool = False):
    from bench import harness

    return harness.run_cell(cell, seed, seconds, traced, root=root,
                            bench=root / "bench", require_tpu=False)
