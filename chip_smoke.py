"""Smoke run of RANL on a TPU: deep-net training and the convex engines.

    python chip_smoke.py              # one chip: training + convex phases
    python chip_smoke.py --chips 4    # only the multi-chip paths, each
                                      # beside what it is compared with

Each phase checks its own results and a failed phase ends the run with a
non-zero exit code.  With no TPU, or run from a directory that is not a
checkout of this repository, the script exits non-zero before it prints
a result.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Every time printed here is a smoke timing, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# train.run arguments: phi4-mini-3.8b at its published widths (d_model
# 3072, d_ff 8192, 24/8 heads of 128), cut to 2 layers and 1/8 of its
# 200,064-token vocabulary so that params, curvature and the 2-worker
# gradient memory fit one v5e's 16 GB
TRAIN_ARCH = "phi4-mini-3.8b"
TRAIN_LAYERS = 2
TRAIN_VOCAB = 25008
# The Newton step scale is 1e-4: at this width the default 1.0 (and 1e-3)
# drive the loss up from the third step, because every step then runs at
# the 10%-of-norm trust-ratio cap; at 1e-4 the cap does not bind.
TRAIN_ARGS = ["--arch", TRAIN_ARCH, "--layers", str(TRAIN_LAYERS),
              "--vocab", str(TRAIN_VOCAB), "--workers", "2", "--batch", "4",
              "--seq", "2048", "--steps", "5", "--lr", "1e-4", "--seed", "0"]
# The first loss is that of random init: logits h.E^T with an RMS-normed
# h and 0.02-scaled embeddings have std ~0.02*sqrt(3072) ~ 1, so the
# cross-entropy starts near ln(vocab) + std^2/2, well inside 1.0 of ln(V).
FIRST_LOSS_ATOL = 1.0
# The multi-chip train run differs from the one-chip run only in the
# order of its f32 reductions (highest matmul precision on both sides);
# four Newton steps divide by the curvature floor and may magnify that
# difference, hence 1e-3 relative rather than f32 epsilon.
TRAIN_SHARDED_RTOL = 1e-3

# convex problem: distributed logistic regression, 16 workers of 2,048
# samples, 4,096 features
CONVEX = dict(num_workers=16, per_worker=2048, dim=4096)
CONVEX_ROUNDS = 30
# The dense runs use the Newton-Schulz projection: the TPU's eigh (the
# default "eigh" projection) does not compile at this d within a host's
# memory.
DENSE = dict(curvature="dense", projection="ns")
# The fused kernel and the jnp oracle do the same elementwise arithmetic
# and differ only in the order of the 16-worker sums (~16 eps relative per
# round); the projected Newton map contracts, so 30 rounds stay within a
# few hundred eps of max|x|.
KERNEL_RTOL = 1e-5
# Sharded engines against the scan engine: the same arithmetic with psum
# reduction orders and, for the 2-D dense path, a blocked Cholesky in
# place of the dense one (error ~ cond(H) * eps); highest precision on
# both sides.
SHARDED_RTOL = 1e-4


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise PhaseFailed(what)


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(1.0, np.abs(b).max()))


def peak_bytes_line(devices) -> str:
    parts = []
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        parts.append(f"dev{d.id}={peak}" if peak is not None
                     else f"dev{d.id}=not reported")
    return "peak_bytes_in_use: " + " ".join(parts)


def run_train(extra=()):
    """train.run on the cut config; checks finite, falling losses."""
    from repro.launch.train import run as train_run
    hist = train_run(TRAIN_ARGS + list(extra))
    losses = [h["loss"] for h in hist]
    check(len(losses) == 5, f"expected 5 logged steps, got {len(losses)}")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    ln_v = math.log(TRAIN_VOCAB)
    check(abs(losses[0] - ln_v) <= FIRST_LOSS_ATOL,
          f"first loss {losses[0]} not within {FIRST_LOSS_ATOL} of "
          f"ln({TRAIN_VOCAB}) = {ln_v}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return hist


def phase_train(devices):
    print(f"== train: {TRAIN_ARCH} via repro.launch.train.run "
          f"{' '.join(TRAIN_ARGS)}", flush=True)
    hist = run_train()
    steady = [h["step_s"] for h in hist[1:]]
    print(f"train losses: {[h['loss'] for h in hist]}")
    print(f"train step_s, steps 1-4, each ending in a device sync "
          f"(smoke, not a benchmark): {steady}")
    print(f"train {peak_bytes_line(devices)} (smoke, not a benchmark)",
          flush=True)


def descends(res, what: str):
    import numpy as np
    losses = np.asarray(res.losses)
    check(bool(np.isfinite(losses).all()), f"{what}: non-finite loss")
    check(bool(np.isfinite(np.asarray(res.xs)).all()),
          f"{what}: non-finite iterate")
    # x1 is the full-curvature Newton step from x0 = 0 and must descend;
    # the later rounds ride stale memory for pruned regions and only reach
    # a neighbourhood of x*, which need not lie below loss(x1)
    check(float(losses[1]) < float(losses[0]),
          f"{what}: first Newton step did not descend "
          f"({losses[0]} -> {losses[1]})")
    return float(losses[0]), float(losses[-1])


def scan_program_text(problem, key, opts) -> str:
    """Compiled HLO of the scan engine's round loop for ``opts`` (the
    same jitted program ``repro.run(engine="scan")`` executes)."""
    from repro.core.ranl import _rounds_jit, _scan_args
    args, static = _scan_args(problem, key, opts, controller=None,
                              cost=None)
    return _rounds_jit.lower(*args, **static).compile().as_text()


def phase_convex(devices):
    import jax
    import repro
    from repro.core import make_logistic

    key = jax.random.PRNGKey(0)
    prob = make_logistic(jax.random.PRNGKey(1), **CONVEX)
    print(f"== convex: repro.run(engine='scan') on make_logistic({CONVEX}), "
          f"{CONVEX_ROUNDS} rounds", flush=True)
    t0 = time.perf_counter()
    dense = repro.run(prob, key, engine="scan", num_rounds=CONVEX_ROUNDS,
                      **DENSE)
    jax.block_until_ready(dense.xs)
    first, last = descends(dense, "scan dense")
    print(f"convex dense: loss {first} -> {last}, "
          f"{time.perf_counter() - t0:.3f}s with compile "
          f"(smoke, not a benchmark)", flush=True)

    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        diag = repro.run(prob, key, engine="scan", num_rounds=CONVEX_ROUNDS,
                         curvature="diag")
        jax.block_until_ready(diag.xs)
        elapsed = time.perf_counter() - t0
        oracle = repro.run(prob, key, engine="scan",
                           num_rounds=CONVEX_ROUNDS, curvature="diag",
                           use_kernel=False)
        hlo = scan_program_text(prob, key, repro.RanlOptions(
            num_rounds=CONVEX_ROUNDS, curvature="diag"))
    first, last = descends(diag, "scan diag (kernel)")
    descends(oracle, "scan diag (jnp oracle)")
    err = rel_err(diag.xs, oracle.xs)
    print(f"convex diag: loss {first} -> {last}, {elapsed:.3f}s with "
          f"compile (smoke, not a benchmark); kernel vs jnp oracle "
          f"rel err {err} (tol {KERNEL_RTOL})", flush=True)
    check(err <= KERNEL_RTOL, f"diag kernel vs oracle rel err {err}")
    check("tpu_custom_call" in hlo, "compiled diag program has no "
          "tpu_custom_call (the kernel ran in interpret mode)")
    print("convex diag program has tpu_custom_call: True")
    print(f"convex {peak_bytes_line(devices)} (smoke, not a benchmark)",
          flush=True)


def phase_multichip_train(devices):
    import jax
    print("== 4 chips: train.run --data-shards 2 --model-shards 2 against "
          "the same steps on one chip", flush=True)
    with jax.default_matmul_precision("highest"):
        sharded = run_train(["--data-shards", "2", "--model-shards", "2"])
        # before the one-chip run, so device 0's peak is the 2x2 run's
        print(f"train 2x2 {peak_bytes_line(devices)}", flush=True)
        single = run_train()
    ls = [h["loss"] for h in sharded]
    l1 = [h["loss"] for h in single]
    err = max(abs(a - b) / abs(b) for a, b in zip(ls, l1))
    print(f"train 2x2 losses {ls}\ntrain 1-chip losses {l1}\n"
          f"train 2x2 vs 1-chip max rel loss diff {err} "
          f"(tol {TRAIN_SHARDED_RTOL})", flush=True)
    check(err <= TRAIN_SHARDED_RTOL, f"sharded train loss rel diff {err}")


def phase_multichip_convex(devices):
    import jax
    import numpy as np
    import repro
    from repro.core import make_logistic
    from repro.launch.mesh import make_engine_mesh, make_mesh

    key = jax.random.PRNGKey(0)
    prob = make_logistic(jax.random.PRNGKey(1), **CONVEX)
    mesh2d = make_engine_mesh(2, 2)
    mesh1d = make_mesh((4,), ("data",))
    print(f"== 4 chips: sharded2d on a 2x2 mesh and sharded on a 4-way "
          f"data mesh against scan, make_logistic({CONVEX}), {CONVEX_ROUNDS} "
          f"rounds", flush=True)
    diag = dict(curvature="diag")
    cases = [(engine, mesh, kw) for engine, mesh in
             (("sharded2d", mesh2d), ("sharded", mesh1d))
             for kw in (DENSE, diag)]
    refs = {}
    with jax.default_matmul_precision("highest"):
        for engine, mesh, kw in cases:
            curv = kw["curvature"]
            if curv not in refs:
                refs[curv] = repro.run(prob, key, engine="scan",
                                       num_rounds=CONVEX_ROUNDS, **kw)
            ref = refs[curv]
            t0 = time.perf_counter()
            res = repro.run(prob, key, engine=engine, mesh=mesh,
                            num_rounds=CONVEX_ROUNDS, **kw)
            jax.block_until_ready(res.xs)
            elapsed = time.perf_counter() - t0
            first, last = descends(res, f"{engine} {curv}")
            err = rel_err(res.xs, ref.xs)
            comm_eq = bool(np.array_equal(np.asarray(res.comm_floats),
                                          np.asarray(ref.comm_floats)))
            print(f"{engine} {curv} {tuple(mesh.devices.shape)}: loss "
                  f"{first} -> {last}, {elapsed:.3f}s with compile (smoke, "
                  f"not a benchmark); vs scan rel err {err} "
                  f"(tol {SHARDED_RTOL}), comm_floats equal {comm_eq}",
                  flush=True)
            check(err <= SHARDED_RTOL, f"{engine} {curv} rel err {err}")
            check(comm_eq, f"{engine} {curv} comm_floats differ")
    print(f"convex 4-chip {peak_bytes_line(devices)}", flush=True)


def _import_repro():
    """Import the package from this checkout's ``src`` and nowhere else."""
    src = os.path.join(HERE, "src")
    sys.path.insert(0, src)
    import repro
    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        raise SystemExit(f"repro imported from {repro.__file__}, not from "
                         f"this checkout ({src})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: training and convex phases on one chip; "
                         "4: only the multi-chip paths")
    args = ap.parse_args(argv)

    import jax
    # a missing chip is an error, never a quiet CPU run
    jax.config.update("jax_platforms", "tpu")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports {dev.platform}")
    print(f"device: {dev.device_kind}, count {len(devices)}", flush=True)
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")

    _import_repro()
    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    hits = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"compile cache: {cache_dir}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        phase_train(devices)
        phase_convex(devices)
    else:
        mesh_devices = devices[:4]
        phase_multichip_train(mesh_devices)
        phase_multichip_convex(mesh_devices)
    print(f"compile cache: {hits['hits']} hits, {hits['misses']} misses; "
          f"all phases {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseFailed as e:
        print(f"phase failed: {e}", file=sys.stderr)
        sys.exit(1)
