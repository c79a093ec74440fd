"""Training driver: RANL (default) or first-order baselines.

Runs end-to-end on host devices at smoke scale and is the same code path the
dry-run lowers at production scale.  Example:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \
      --steps 20 --batch 8 --seq 64 --workers 4

With ``--data-shards N`` the RANL worker/batch axes shard over an
(N,)-device ``("data",)`` mesh (workers and batch must divide by N).
Adding ``--model-shards M`` upgrades it to an (N, M) ``("data","model")``
mesh: the parameter/tensor axes additionally shard over "model" via the
PartitionSpec rules in ``launch/shard.py``, so per-device optimizer state
(params, curvature, the N×params gradient memory) drops by ~M on top of
the worker split.  ``--pods P`` prepends a pod axis — the full
(P, N, M) ``("pod","data","model")`` mesh of the hierarchical engines,
pod-major device order, with the worker/batch axes sharding jointly over
("pod","data").  On a laptop/CI set
``XLA_FLAGS=--xla_force_host_platform_device_count=P*N*M`` to emulate
the devices.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

from ..configs import get_config, smoke_variant
from ..data import make_batch
from ..models import init_model, lm_loss
from ..obs import Journal, Tracer, make_header, span, tracing
from ..optim import (AdamWConfig, RanlLLMConfig, adamw_init, adamw_step,
                     init_state, train_step)
from ..checkpoint import save
from ..models.sharding import use_mesh
from .cache import use_compile_cache


def attn_block(seq: int) -> int:
    """Query and key block of the blocked attention over ``seq`` tokens:
    1,024, or half the sequence beyond 2,048 (fewer, larger blocks
    compile faster at long sequences), never more than ``seq``."""
    return min(seq, max(1024, seq // 2))


def build_loss(cfg, q_chunk=1024, kv_chunk=1024, remat=True):
    """The train loss; with MoE layers it also returns their routing
    counters (``loss_fn.has_aux``), which ``train_step`` reports."""
    with_stats = bool(cfg.num_experts)

    def loss_fn(params, batch):
        return lm_loss(params, batch, cfg, q_chunk=q_chunk,
                       kv_chunk=kv_chunk, remat=remat, with_stats=with_stats)
    loss_fn.has_aux = with_stats
    return loss_fn


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the registered config to this many layers "
                         "(widths unchanged; 0 = published depth)")
    ap.add_argument("--vocab", type=int, default=0,
                    help="cut the registered config to this vocabulary "
                         "size (widths unchanged; 0 = published vocab)")
    ap.add_argument("--experts-held", type=int, default=0,
                    help="hold this many of the routed experts (the "
                         "router still scores all of them; 0 = all)")
    ap.add_argument("--optimizer", default="ranl",
                    choices=["ranl", "adamw"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--data-shards", type=int, default=1,
                    help="shard the worker/batch axes over this many "
                         "devices of a ('data',) mesh (1 = unsharded)")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="additionally shard parameter/tensor axes over "
                         "this many devices of the 'model' axis of a "
                         "('data','model') mesh (1 = data-parallel only)")
    ap.add_argument("--pods", type=int, default=1,
                    help="prepend a 'pod' axis: the worker/batch axes "
                         "shard jointly over the (pods, data_shards) "
                         "('pod','data') plane of the 3-D "
                         "('pod','data','model') mesh, pod-major device "
                         "order (1 = no pod axis)")
    ap.add_argument("--dump-hlo", default="", metavar="PATH",
                    help="lower + compile the train step, write the "
                         "partitioned HLO text to PATH, print the "
                         "hlo_analysis report (largest per-device buffer, "
                         "collective inventory), and exit without "
                         "training — the CLI form of the memory/"
                         "communication assertions the engine tests pin")
    ap.add_argument("--scenario", default="",
                    help="named cluster scenario (repro.hetero), e.g. "
                         "'pareto-stragglers' or 'churn:period=5' — prices "
                         "every round under the per-worker cost model, "
                         "applies its availability dynamics to the masks, "
                         "and logs simulated wall-clock (sim_s)")
    ap.add_argument("--controller", default="",
                    help="closed-loop mask controller (repro.hetero), "
                         "e.g. 'resource:keep=0.7' or "
                         "'staleness-bounded:s=4' — allocates each "
                         "round's regions from the previous round's "
                         "telemetry instead of the open-loop policy")
    ap.add_argument("--quorum", type=float, default=0.0,
                    help="semi-synchronous rounds: commit once this "
                         "fraction of regions has on-time coverage (the "
                         "k-th order statistic of simulated worker "
                         "times) and DROP late workers from the step — "
                         "the gamma=0 limit of the engines' late-fold "
                         "path (repro.run quorum=...). 0 = synchronous. "
                         "Needs --scenario/--controller")
    ap.add_argument("--quorum-tau", type=int, default=1,
                    help="per-region on-time coverage floor for "
                         "--quorum (0 = full participating coverage)")
    ap.add_argument("--compression", default="",
                    choices=["", "int8", "bf16"],
                    help="lossy uplink compression of the per-worker "
                         "gradients before the aggregate (RANL only; "
                         "empty = exact f32 wire)")
    ap.add_argument("--keep-prob", type=float, default=0.7)
    ap.add_argument("--mu", type=float, default=1e-4)
    ap.add_argument("--lr", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pattern", default="bigram",
                    choices=["bigram", "uniform"])
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--journal", default="", metavar="PATH",
                    help="write a structured run journal (JSONL, "
                         "repro.obs schema): header + one record per "
                         "step + summary — render it with "
                         "'python -m repro.obs.report PATH'")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="record the run's ranl.train.* spans (lower/"
                         "compile/execute/checkpoint) and write "
                         "Chrome-trace JSON to PATH (open in Perfetto); "
                         "spans also land in the --journal when both are "
                         "set")
    args = ap.parse_args(argv)
    tracer = Tracer() if args.trace else None
    with tracing(tracer) if tracer is not None else nullcontext():
        return _train(args, tracer)


def _train(args, tracer):
    use_compile_cache()
    if args.dump_hlo and args.optimizer != "ranl":
        raise SystemExit("--dump-hlo reports the RANL train step; rerun "
                         "with --optimizer ranl (the baseline optimizers "
                         "have no lowered step to analyze here)")
    if args.quorum and not (args.scenario or args.controller):
        raise SystemExit("--quorum needs the simulated cluster clock — "
                         "pass --scenario and/or --controller")
    if args.quorum and not 0.0 < args.quorum <= 1.0:
        raise SystemExit(f"--quorum {args.quorum} must be in (0, 1]")
    if (args.scenario or args.controller) and args.optimizer != "ranl":
        raise SystemExit("--scenario/--controller drive the RANL "
                         "region-mask loop; rerun with --optimizer ranl")
    if args.compression and args.optimizer != "ranl":
        raise SystemExit("--compression shapes the RANL uplink; rerun "
                         "with --optimizer ranl")

    cfg = get_config(args.arch)
    if args.smoke:
        if args.layers or args.vocab or args.experts_held:
            raise SystemExit("--layers/--vocab/--experts-held cut the "
                             "published config; --smoke already replaces "
                             "it")
        cfg = smoke_variant(cfg)
    elif args.layers or args.vocab or args.experts_held:
        if min(args.layers, args.vocab, args.experts_held) < 0:
            raise SystemExit("--layers/--vocab/--experts-held must be "
                             "positive")
        if args.experts_held > cfg.num_experts:
            raise SystemExit(f"--experts-held {args.experts_held}: "
                             f"{args.arch} routes over {cfg.num_experts}")
        cut = {}
        if args.layers:
            cut["num_layers"] = args.layers
        if args.vocab:
            cut["vocab_size"] = args.vocab
        if args.experts_held:
            cut["experts_held"] = args.experts_held
        cfg = dataclasses.replace(cfg, **cut)
        held = (f" experts held={cfg.held_experts}/{cfg.num_experts}"
                if cfg.num_experts else "")
        print(f"config cut: {args.arch} layers={cfg.num_layers} "
              f"vocab={cfg.vocab_size}{held} (d_model={cfg.d_model} "
              f"d_ff={cfg.d_ff} heads={cfg.num_heads}/{cfg.num_kv_heads} "
              f"x{cfg.resolved_head_dim} as published)")
    mesh = None
    if args.pods < 1:
        raise SystemExit(f"--pods {args.pods} must be >= 1")
    if args.pods > 1 or args.model_shards > 1:
        from .mesh import make_engine_mesh
        try:
            mesh = make_engine_mesh(args.data_shards, args.model_shards,
                                    pods=args.pods)
        except ValueError as e:
            raise SystemExit(str(e)) from e
        print(f"mesh: {tuple(mesh.devices.shape)} {mesh.axis_names} "
              f"over {jax.devices()[0].platform}")
    elif args.data_shards > 1:
        ndev = jax.device_count()
        if ndev < args.data_shards:
            raise SystemExit(
                f"--data-shards {args.data_shards} needs that many devices "
                f"but jax sees {ndev}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count="
                f"{args.data_shards} to emulate them")
        from .mesh import make_mesh
        mesh = make_mesh((args.data_shards,), ("data",))
        print(f"mesh: {args.data_shards}-way ('data',) over "
              f"{jax.devices()[0].platform}")
    # model code reads the mesh (sharding hints, the expert layer's
    # shard_map) from the ambient context
    with use_mesh(mesh) if mesh is not None else nullcontext():
        return _train_loop(args, tracer, cfg, mesh)


def _train_loop(args, tracer, cfg, mesh):
    key = jax.random.PRNGKey(args.seed)
    kp, kd, ko = jax.random.split(key, 3)

    params = init_model(cfg, kp)
    chunk = attn_block(args.seq)
    loss_fn = build_loss(cfg, q_chunk=chunk, kv_chunk=chunk)
    batch0 = make_batch(cfg, jax.random.fold_in(kd, 0),
                        args.batch, args.seq, pattern=args.pattern)

    history = []
    journal = Journal(args.journal) if args.journal else None

    if args.optimizer == "ranl":
        rcfg = RanlLLMConfig(num_workers=args.workers,
                             keep_prob=args.keep_prob, mu=args.mu,
                             lr=args.lr,
                             compression=args.compression or None)
        state = init_state(params, loss_fn, batch0, rcfg, ko, mesh=mesh)
        step_fn = jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg,
                                  mesh=mesh))
        # closed-loop heterogeneity: controller state + telemetry live
        # host-side (the training loop is a host loop), each step's mask
        # allocation is passed into the jitted step via masks=
        hetero = None
        if args.scenario or args.controller:
            from ..hetero import (available, initial_telemetry,
                                  make_controller, make_scenario,
                                  next_telemetry, quorum_split,
                                  uniform_cost, worker_times)
            from ..optim import region_layout, region_param_counts
            num_regions, _, _ = region_layout(params)
            scen = (make_scenario(args.scenario, jax.random.fold_in(ko, 71),
                                  args.workers)
                    if args.scenario else None)
            cost = scen.cost if scen else uniform_cost(args.workers)
            ctrl = make_controller(
                args.controller if args.controller
                else f"policy:keep={args.keep_prob}")
            sizes_q = region_param_counts(params)
            hetero = dict(
                ctrl=ctrl, cost=cost, sizes_q=sizes_q,
                num_regions=num_regions,
                ctrl_state=ctrl.init_state(args.workers, num_regions),
                telem=initial_telemetry(args.workers, num_regions),
                sim_s=0.0)
            if scen:
                print(f"scenario: {scen.name} (controller "
                      f"{args.controller or 'policy shim'})")
        def _header(hlo=None):
            return make_header(
                engine="train:ranl", options=rcfg, mesh=mesh,
                scenario=args.scenario or None, hlo=hlo,
                extra={"arch": args.arch, "steps": args.steps,
                       "batch": args.batch, "seq": args.seq,
                       "controller": args.controller or None,
                       "quorum": args.quorum or None})

        if args.dump_hlo:
            from .hlo_analysis import cost_raw_summary, module_report
            from ..obs import hlo_header
            with span("ranl.train.lower"):
                lowered = step_fn.lower(params, state, batch0, ko)
            with span("ranl.train.compile"):
                compiled = lowered.compile()
            txt = compiled.as_text()
            with open(args.dump_hlo, "w") as f:
                f.write(txt)
            rep = module_report(txt)
            if journal is not None:
                # surface the compiled program's byte totals next to the
                # contract key so a journal alone answers what this
                # program put on the wire and held per device
                journal.write(_header(
                    hlo=hlo_header(rep, cost_raw_summary(compiled))))
                if tracer is not None:
                    for srec in tracer.span_records():
                        journal.write(srec)
                journal.close()
                print(f"wrote journal to {args.journal}")
            rep["records"] = rep["records"][:12]      # top movers only
            print(f"wrote partitioned HLO to {args.dump_hlo}")
            print(json.dumps(rep, indent=2))
            return rep
        if journal is not None:
            journal.write(_header())
        exec_fn = None
        for t in range(args.steps):
            batch = make_batch(cfg, jax.random.fold_in(kd, t + 1),
                               args.batch, args.seq, pattern=args.pattern)
            masks = None
            if hetero is not None:
                kt = jax.random.fold_in(ko, t)
                masks, hetero["ctrl_state"] = hetero["ctrl"].step(
                    hetero["ctrl_state"], hetero["telem"], kt, t,
                    args.workers, hetero["num_regions"])
                avail = available(hetero["cost"], kt, t)
                masks = jnp.logical_and(masks, avail[:, None])
                if args.quorum:
                    # semi-synchronous drop mode: the round commits at
                    # the quorum deadline and late workers sit it out
                    # (their regions ride the optimizer's memory path)
                    work = (masks * hetero["sizes_q"][None, :]) \
                        .sum(axis=1)
                    times = worker_times(hetero["cost"], work, t)
                    deadline, on_time, _ = quorum_split(
                        times, masks, quorum=args.quorum,
                        quorum_tau=args.quorum_tau or None)
                    masks = jnp.logical_and(masks, on_time[:, None])
                    hetero["deadline"] = float(deadline)
            if tracer is not None and exec_fn is None:
                # AOT split so lowering/compile time is attributable
                # (the jit path would fold both into the first execute)
                with span("ranl.train.lower"):
                    low = step_fn.lower(params, state, batch, ko,
                                        masks=masks)
                with span("ranl.train.compile"):
                    exec_fn = low.compile()
            fn = exec_fn if exec_fn is not None else step_fn
            t0 = time.perf_counter()
            with span("ranl.train.execute", step=t):
                params, state, metrics = fn(params, state, batch, ko,
                                            masks=masks)
            sim_note = ""
            if hetero is not None:
                work = (masks * hetero["sizes_q"][None, :]).sum(axis=1)
                times = worker_times(hetero["cost"], work, t)
                hetero["telem"] = next_telemetry(
                    hetero["telem"], masks.sum(axis=0), work, times)
                hetero["sim_round_s"] = (hetero["deadline"]
                                         if args.quorum
                                         else float(times.max()))
                hetero["sim_s"] += hetero["sim_round_s"]
                hetero["max_stale"] = int(hetero["telem"].stale_q.max())
                sim_note = (f" sim_s={hetero['sim_s']:.0f} "
                            f"stale<={hetero['max_stale']}")
            if (journal is not None or t % args.log_every == 0
                    or t == args.steps - 1):
                # the ONLY device round-trip: unrecorded steps leave the
                # metrics on device and the dispatch queue stays async
                metrics = {k: float(v) for k, v in metrics.items()}
                metrics["step_s"] = time.perf_counter() - t0
                if hetero is not None:
                    metrics["sim_round_s"] = hetero["sim_round_s"]
                    metrics["sim_s"] = hetero["sim_s"]
                    metrics["max_stale"] = hetero["max_stale"]
                history.append(metrics)
                if journal is not None:
                    journal.write({"kind": "round", "t": t + 1, **metrics})
                if t % args.log_every == 0:
                    moe_note = ""
                    if "held_rows" in metrics:
                        moe_note = (
                            f" rows held={metrics['held_rows']:.0f} "
                            f"absent={metrics['absent_rows']:.0f} "
                            f"dropped={metrics['dropped_rows']:.0f} "
                            f"load_max={metrics['load_max_ratio']:.2f}")
                    print(f"step {t:4d} loss={metrics['loss']:.4f} "
                          f"cov={metrics['coverage']:.2f} "
                          f"uplink={metrics['uplink_frac']:.2f} "
                          f"({metrics['step_s']:.2f}s){sim_note}{moe_note}")
    else:
        acfg = AdamWConfig(lr=1e-3)
        state = adamw_init(params, acfg)
        if journal is not None:
            journal.write(make_header(
                engine="train:adamw", options=acfg, mesh=mesh,
                extra={"arch": args.arch, "steps": args.steps,
                       "batch": args.batch, "seq": args.seq}))

        @jax.jit
        def astep(params, state, batch):
            loss, g = jax.value_and_grad(loss_fn, has_aux=loss_fn.has_aux)(
                params, batch)
            if loss_fn.has_aux:
                loss = loss[0]
            params, state = adamw_step(params, state, g, acfg)
            return params, state, loss

        for t in range(args.steps):
            batch = make_batch(cfg, jax.random.fold_in(kd, t + 1),
                               args.batch, args.seq, pattern=args.pattern)
            with span("ranl.train.execute", step=t):
                params, state, loss = astep(params, state, batch)
            if (journal is not None or t % args.log_every == 0
                    or t == args.steps - 1):
                rec = {"loss": float(loss)}
                history.append(rec)
                if journal is not None:
                    journal.write({"kind": "round", "t": t + 1, **rec})
                if t % args.log_every == 0:
                    print(f"step {t:4d} loss={rec['loss']:.4f}")

    if args.checkpoint_dir:
        with span("ranl.train.checkpoint"):
            save(params, args.checkpoint_dir, step=args.steps)
        print(f"saved checkpoint to {args.checkpoint_dir}")
    if journal is not None:
        if tracer is not None:
            for srec in tracer.span_records():
                journal.write(srec)
        journal.write({"kind": "summary", "rounds": args.steps,
                       "first_loss": history[0]["loss"],
                       "final_loss": history[-1]["loss"]})
        journal.close()
        print(f"wrote journal to {args.journal}")
    if tracer is not None:
        tracer.write_chrome(args.trace)
        print(f"wrote chrome trace to {args.trace}")
    print(json.dumps({"final_loss": history[-1]["loss"],
                      "first_loss": history[0]["loss"]}))
    return history


if __name__ == "__main__":
    run()
