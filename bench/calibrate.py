"""Readings from which the limits of ``correct`` are set.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control]
        [--faults] [--seconds 3] [--out FILE]

For each seed, in one process: the program's compared numbers against the
plain reference (the lower readings), and with ``--control`` the
reference computed one precision lower in the program's place, with
``--faults`` the program with each planted fault the cell can have (the
upper readings).  Prints one JSON line per seed and reading.  Not run by
the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import faults, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    spec, cell, config, traffic = run.resolve_cell(args.workload)
    peaks = run.load_json(os.path.join("bench", "peaks.json"))
    run.use_compile_cache()
    devices = run.require_devices(cell["chips"], peaks)
    out = open(args.out, "a") if args.out else None
    kinds = ["program"] + (["control"] if args.control else []) \
        + (list(faults.for_config(config)) if args.faults else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        ref = None
        for kind in kinds:
            face = run.make_face(config, traffic, devices, seed,
                                 cell["name"])
            with faults.planted(kind, face):
                got = faults.readings(face, kind, args.seconds, ref)
            ref = got.pop("_ref")
            rec = {"workload": cell["name"], "seed": seed, "kind": kind,
                   **got}
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    main()
