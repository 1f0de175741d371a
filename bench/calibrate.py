"""Readings that the limits of a cell's comparison are set from.

  python3 bench/calibrate.py --workload albert-large.btard.1chip \
      --seeds 11 12 13 --control-seeds 11 12 13

For each seed, in one process: the program's first steps against the
reference (the lower readings), and for the control seeds the float8
control and each fault the cell can have, in the program's place (the upper
readings). One JSON line per seed and reading, then a summary line. The
benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "grad_gap_median",
           "update_gap_median", "grad_diff")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--cpu", action="store_true",
                    help="run without a chip (small test cells only)")
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args(argv)

    from bench import catalog, compare, harness, reference

    root = Path(args.root)
    bm = catalog.load_benchmark(root)
    cell = catalog.workload(bm, args.workload)
    config = catalog.config(bm, cell["config"], root)
    traffic = catalog.traffic(cell["traffic"], root / "bench")
    if not args.cpu:
        harness.require_chips(cell["chips"])
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    n_steps = harness.CHECK_CHUNKS * traffic["scan_steps"]
    faults = ["half_batch"] + (["no_exchange"] if traffic["mesh"][0] > 1 else [])
    prog = harness.Program(config, traffic)
    summary = {}

    def emit(seed, what, nums, **extra):
        row = {"seed": seed, "reading": what,
               **{k: nums[k][0] for k in NUMBERS}, **extra,
               "leaves": {k: nums[k][1] for k in ("grad_gap", "update_gap")}}
        print(json.dumps(row), flush=True)
        summary.setdefault(what, []).append(row)

    for seed in args.seeds:
        prog.start(seed)
        got = prog.first_steps()
        prog.free()
        base = harness.base_step(seed)
        ref = reference.follow(config, traffic, seed, base, n_steps)
        emit(seed, "program", compare.numbers(got, ref),
             checksum_accused=prog.accused)
        if seed in args.control_seeds:
            for mode, fault in [("fp8", None)] + [("f32", f) for f in faults]:
                alt = reference.follow(config, traffic, seed, base, n_steps,
                                       mode=mode, fault=fault)
                emit(seed, fault or "control_fp8", compare.numbers(alt, ref))
    out = {}
    for what, rows in summary.items():
        agg = max if what == "program" else min
        out[what] = {k: agg(r[k] for r in rows) for k in NUMBERS}
        out[what]["seeds"] = len(rows)
    print(json.dumps({"summary": out, "workload": args.workload}), flush=True)


if __name__ == "__main__":
    main()
