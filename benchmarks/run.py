"""Benchmark harness: one entry per paper claim (the paper is a theory
paper with no experiment tables — DESIGN.md §7 maps claims to benches)
plus kernel micro-benches and, when dry-run artifacts exist, the roofline
summary.

Prints ``name,us_per_call,derived`` CSV.  Run:
  PYTHONPATH=src python -m benchmarks.run [--only claims|kernels|roofline]
                                          [--smoke] [--json OUT.json]

``--smoke`` shrinks every bench to tiny shapes / few rounds (interpret-mode
Pallas) so the whole sweep finishes in a couple of minutes — the CI smoke
job runs it and uploads ``--json`` output as an artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "claims", "kernels", "roofline"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few rounds; skips roofline")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows as a JSON file")
    ap.add_argument("--engine-json", default=None, metavar="OUT",
                    help="also write the engine/* rows (the perf "
                         "trajectory the CI tracks) as a JSON file")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    rows = []
    if args.only in (None, "claims"):
        from . import claims
        for fn in (claims.bench_convergence, claims.bench_condition,
                   claims.bench_staleness, claims.bench_coverage,
                   claims.bench_heterogeneity,
                   claims.bench_second_order_baselines,
                   claims.bench_comm_cost,
                   claims.bench_engine_speedup,
                   claims.bench_batch_seeds,
                   claims.bench_sharded_engine,
                   claims.bench_sharded2d_engine,
                   claims.bench_diag_kernel_path,
                   claims.bench_init_projection,
                   claims.bench_overlap,
                   claims.bench_hierarchy,
                   claims.bench_hetero,
                   claims.bench_quorum,
                   claims.bench_compression,
                   claims.bench_obs_overhead):
            rows.extend(fn(smoke=args.smoke))
    if args.only in (None, "kernels"):
        from . import kernels_bench as kb
        for fn in (kb.bench_region_aggregate, kb.bench_ranl_update,
                   kb.bench_flash_attention, kb.bench_rwkv_wkv):
            rows.extend(fn(smoke=args.smoke))

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"# wrote {len(rows)} rows to {args.json}")

    if args.engine_json:
        eng = [r for r in rows if r["name"].startswith("engine/")]
        with open(args.engine_json, "w") as f:
            json.dump(eng, f, indent=2)
        print(f"# wrote {len(eng)} engine rows to {args.engine_json}")
        if args.only in (None, "claims"):
            # a renderable run journal rides along with every engine
            # bench artifact (python -m repro.obs.report <path>)
            from . import claims
            jpath = os.path.splitext(args.engine_json)[0] + ".journal.jsonl"
            claims.write_bench_journal(jpath, smoke=args.smoke)
            print(f"# wrote engine bench journal to {jpath}")

    if args.only in (None, "roofline") and not args.smoke:
        dr = os.path.join(os.path.dirname(__file__), "..",
                          "experiments", "dryrun")
        if os.path.isdir(dr) and os.listdir(dr):
            from . import roofline
            print()
            roofline.main()
        else:
            print("# roofline: no dry-run artifacts "
                  "(run repro.launch.dryrun first)")


if __name__ == "__main__":
    main()
