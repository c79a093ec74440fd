import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh).

The two lines above MUST run before any jax import (jax locks the device
count at first init); they exist only here — tests and benches see the real
single device.

For each combination this produces, into experiments/dryrun/:
  * proof of lowering/compilation on the production mesh,
  * compiled.memory_analysis() (per-device bytes — the "fits" proof),
  * compiled.cost_analysis() raw FLOPs/bytes (scan bodies counted once),
  * per-layer differenced FLOPs/bytes from unrolled 1-/2-layer cost graphs
    (exact per-layer accounting; see EXPERIMENTS.md §Dry-run methodology),
  * the collective inventory (kind/bytes/loop-multiplier) parsed from the
    partitioned HLO.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape train_4k --mesh pod1
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1 pod2
"""

import argparse
import dataclasses
import json
import time
import traceback


def _mesh_by_name(name: str):
    from .mesh import make_mesh, make_production_mesh
    if name == "pod1":
        return make_production_mesh(multi_pod=False)
    if name == "pod2":
        return make_production_mesh(multi_pod=True)
    if name.startswith("tiny"):        # tiny8 -> (2,4); tiny2x4 etc.
        return make_mesh((2, 4), ("data", "model"))
    raise ValueError(name)


def lower_and_compile(cfg, shape, mesh, *, scan_layers=True,
                      compile_graph=True):
    """Returns result dict (everything JSON-serializable)."""
    from ..models.sharding import use_mesh
    from .hlo_analysis import (collect_collectives, cost_raw_summary,
                               summarize_collectives)
    from .steps import make_bundle
    import jax

    out = {"arch": cfg.name, "shape": shape.name,
           "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
           "num_devices": mesh.devices.size, "ok": False}
    t0 = time.perf_counter()
    with use_mesh(mesh):
        bundle = make_bundle(cfg, shape, mesh, scan_layers=scan_layers)
        # donate params/state (train) or cache (decode): outputs alias
        # inputs, halving resident framework state — matches real training
        donate = (0, 1) if bundle.name in ("train", "decode") else ()
        jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=donate)
        lowered = jitted.lower(*bundle.abstract_args)
    out["step"] = bundle.name
    out["meta"] = bundle.meta
    out["lower_s"] = time.perf_counter() - t0
    if not compile_graph:
        out["ok"] = True
        return out

    t0 = time.perf_counter()
    compiled = lowered.compile()
    out["compile_s"] = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    out["memory"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "total_bytes": (mem.argument_size_in_bytes
                        + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes
                        - mem.alias_size_in_bytes),
    }
    out["cost_raw"] = cost_raw_summary(compiled)
    txt = compiled.as_text()
    recs = collect_collectives(txt, default_trip=cfg.num_layers)
    out["collectives"] = summarize_collectives(recs)
    out["ok"] = True
    return out


def cost_graphs(cfg, shape, mesh):
    """Per-layer differenced cost: unrolled 1- and 2-layer graphs."""
    results = {}
    for L in (1, 2):
        c = dataclasses.replace(cfg, num_layers=L)
        r = lower_and_compile(c, shape, mesh, scan_layers=False)
        results[f"L{L}"] = {"cost_raw": r["cost_raw"],
                            "collectives": r["collectives"],
                            "memory": r["memory"]}
    f1 = results["L1"]["cost_raw"].get("flops", 0.0)
    f2 = results["L2"]["cost_raw"].get("flops", 0.0)
    b1 = results["L1"]["cost_raw"].get("bytes accessed", 0.0)
    b2 = results["L2"]["cost_raw"].get("bytes accessed", 0.0)
    L = cfg.num_layers
    results["derived"] = {
        "flops_per_layer": f2 - f1,
        "bytes_per_layer": b2 - b1,
        "flops_total": f1 + (L - 1) * (f2 - f1),
        "bytes_total": b1 + (L - 1) * (b2 - b1),
        "num_layers": L,
    }
    return results


def main(argv=None):
    from ..configs import ALL_ARCHS, INPUT_SHAPES, get_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", nargs="*", default=["pod1"],
                    choices=["pod1", "pod2", "tiny8"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cost-graphs", action="store_true",
                    help="also compile unrolled 1/2-layer cost graphs")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    archs = args.arch or (ALL_ARCHS if args.all else ["phi4-mini-3.8b"])
    shapes = args.shape or (list(INPUT_SHAPES) if args.all
                            else ["train_4k"])
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for mesh_name in args.mesh:
        mesh = _mesh_by_name(mesh_name)
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                shape = INPUT_SHAPES[shape_name]
                tag = f"{mesh_name}__{arch}__{shape_name}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    res = lower_and_compile(
                        cfg, shape, mesh,
                        compile_graph=not args.no_compile)
                    if args.cost_graphs:
                        res["cost_graphs"] = cost_graphs(cfg, shape, mesh)
                except Exception as e:  # noqa: BLE001 — record and continue
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = "OK " if res.get("ok") else "FAIL"
                mem = res.get("memory", {}).get("total_bytes", 0) / 2**30
                print(f"[{status}] {tag}  mem/dev={mem:.2f}GiB "
                      f"lower={res.get('lower_s', 0):.1f}s "
                      f"compile={res.get('compile_s', 0):.1f}s",
                      flush=True)
    print(f"done, failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
