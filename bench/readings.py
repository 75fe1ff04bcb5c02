"""Readings that a cell's correctness limits are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 10

In one process (set-up is long), for each seed: build the cell as
``bench/run.py`` does, run a window of ``--seconds``, free the program's
state, and print one JSON line with the compared numbers of the program
and of every stand-in the job defines in the program's place (the
control, a precision lower, and the planted faults).
The limit of each number goes between the largest sound reading over a
dozen seeds or more and the smallest reading of a stand-in that it must
reject (``PERF.md`` gives both).  Not part of a benchmark run.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run
    from bench.lib import device, spec as spec_lib, window
    from repro.launch import compile_cache

    spec = spec_lib.Spec.load(ROOT)
    try:
        dev = device.require(spec.cell(args.workload)["chips"])
    except device.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 3
    compile_cache.enable()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx, job = run.build(spec, args.workload, seed, args.seconds, False)
        with window.Window(args.seconds) as win:
            job.run(win)
        job.after_window()
        job.release()
        row = {"seed": seed, "device": dev["kind"],
               "program": {k: c["value"] for k, c in job.check().items()}}
        if hasattr(job, "worst_leaf"):
            row["program_worst_leaf"] = job.worst_leaf
        for s in job.STAND_INS:
            row[s] = {k: c["value"] for k, c in job.check(s).items()}
        print(json.dumps(row), flush=True)
        del ctx, job
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
