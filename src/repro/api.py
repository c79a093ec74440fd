"""``repro.run`` / ``repro.lower`` — one dispatcher over the engine family.

Every engine (the compiled ``lax.scan`` loop, the vmapped multi-seed
batch, the worker-sharded and dimension-sharded placements of the one
``shard_map`` round loop, and the eager host-loop reference oracle) runs
the same Algorithm 1.  This module is their one surface:

    import repro
    result = repro.run(problem, key, engine="sharded",
                       options=repro.RanlOptions(num_rounds=50,
                                                 quorum=0.75),
                       mesh=mesh)

``options`` is one frozen, hashable :class:`~repro.core.options.RanlOptions`
record (construction-time validated); ``mesh``, the axis names, and the
heterogeneity objects (``controller``/``cost``) stay call arguments
because they are environment, not algorithm configuration.  Keyword
``**overrides`` merge into ``options`` for one-liners:
``repro.run(problem, key, num_rounds=5)``.

``repro.lower`` is the matching compile-only surface for the two sharded
engines (the HLO the memory/communication assertions inspect).

Engine-compatibility rules enforced here, before any trace:

* ``"sharded"``/``"sharded2d"`` require ``mesh``; ``"scan"`` and
  ``"reference"`` reject one (``"batch"`` uses it to shard seeds);
* ``overlap=True`` exists only on the sharded engines;
* ``"reference"`` is the dense-``eigh`` oracle — ``curvature="diag"``
  or ``projection="ns"`` there is an error;
* ``projection="eigh"`` on the 2-D dense path is rejected (no device
  may hold a d×d buffer — the engine's default there is ``"ns"``);
* ``hessian_rank`` (the low-rank [H]_μ init) exists only where the
  dense init materializes per-worker Hessians — the reference oracle
  and the panel-sharded 2-D dense init reject it;
* ``hierarchy="pods=..."`` (pod-of-pods aggregation) exists on the
  compiled engines only — the eager reference oracle rejects it; on the
  sharded engines the ``mesh`` must carry the ``pod_axis`` with exactly
  ``pods`` shards (checked at trace);
* a :class:`~repro.hetero.controller.QuorumController` unwraps: its
  quorum knobs move onto the options (setting ``options.quorum`` too is
  a conflict) and its inner controller drives mask allocation.
"""

from __future__ import annotations

from .core.options import RanlOptions  # noqa: F401
from .core.ranl import (
    RanlResult,  # noqa: F401
    _lower_sharded,
    _run_batch,
    _run_reference,
    _run_scan,
    _run_sharded,
    trace_ranl,
)
from .obs.trace import span

ENGINES = ("scan", "batch", "sharded", "sharded2d", "reference")
_MESH_REQUIRED = ("sharded", "sharded2d")
_MESH_FORBIDDEN = ("scan", "reference")


def _resolve(engine, options, mesh, controller, overrides):
    """Shared validation for run/lower -> (options, controller)."""
    from .hetero.controller import QuorumController, make_controller
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} "
                         f"(expected one of {ENGINES})")
    opts = RanlOptions() if options is None else options
    if not isinstance(opts, RanlOptions):
        raise TypeError(f"options must be a RanlOptions, got {opts!r}")
    if overrides:
        opts = opts.merged(**overrides)
    if engine in _MESH_REQUIRED and mesh is None:
        raise ValueError(f"engine {engine!r} needs a mesh= argument")
    if engine in _MESH_FORBIDDEN and mesh is not None:
        raise ValueError(f"engine {engine!r} takes no mesh — use "
                         f"'sharded'/'sharded2d' (or 'batch' to shard "
                         f"seeds)")
    if opts.overlap and engine not in _MESH_REQUIRED:
        raise ValueError(f"overlap=True only exists on the sharded "
                         f"engines, not {engine!r}")
    if engine == "reference":
        if opts.curvature != "dense":
            raise ValueError("the reference engine is the dense-eigh "
                             "oracle — curvature='diag' has no host-loop "
                             "form")
        if opts.projection == "ns":
            raise ValueError("the reference engine is the dense-eigh "
                             "oracle — projection='ns' has no host-loop "
                             "form")
        if opts.hessian_rank is not None:
            raise ValueError("the reference engine is the dense-eigh "
                             "oracle — hessian_rank has no host-loop "
                             "form (use engine='scan')")
        if opts.hierarchy is not None:
            raise ValueError("hierarchy= (pod-of-pods aggregation) has "
                             "no host-loop form on the reference oracle "
                             "— use engine='scan' or a sharded engine "
                             "on a pod mesh")
    if engine == "sharded2d" and opts.hessian_rank is not None:
        raise ValueError(
            "hessian_rank is not implementable on the 2-D engine: its "
            "dense init is panel-sharded (no device may hold the d×d "
            "buffer the rank-r eigh fold reads) — use engine='scan', "
            "'batch' or 'sharded'")
    if isinstance(controller, str):
        controller = make_controller(controller)
    if isinstance(controller, QuorumController):
        if opts.quorum is not None:
            raise ValueError(
                "quorum is configured twice: on the QuorumController AND "
                "on RanlOptions — set it in exactly one place")
        opts = opts.merged(quorum=controller.quorum,
                           quorum_tau=controller.quorum_tau,
                           gamma=controller.gamma,
                           max_delay=controller.max_delay)
        controller = controller.inner
    return opts, controller


def run(problem, key, *, engine: str = "scan",
        options: RanlOptions | None = None, mesh=None,
        axis_name: str = "data", data_axis: str = "data",
        model_axis: str = "model", pod_axis: str = "pod",
        controller=None, cost=None, journal=None, scenario=None,
        **overrides):
    """Run Algorithm 1 on ``problem`` with the chosen engine.

    ``key``: a PRNG key — or (B,)-stacked keys for ``engine="batch"``
    (whose result carries a leading seed axis).  ``controller`` may be a
    Controller instance, a ``make_controller`` spec string, or ``None``
    (the options' open-loop policy); ``cost`` a ``CostModel`` or ``None``
    (uniform).  ``journal`` (a path or ``repro.obs.Journal``) records the
    finished run — header, per-round traces, drift alarms, active spans,
    summary — entirely host-side after the engine returns: the compiled
    program is identical with or without it.  ``scenario`` labels the
    journal header (defaults to the cost model's scenario name when it
    has one).  Remaining ``**overrides`` are ``RanlOptions`` fields
    merged into ``options``.  Returns :class:`RanlResult`.
    """
    opts, controller = _resolve(engine, options, mesh, controller,
                                overrides)
    with span("ranl.run", engine=engine):
        if engine == "scan":
            result = _run_scan(problem, key, opts, controller=controller,
                               cost=cost)
        elif engine == "batch":
            result = _run_batch(problem, key, opts, mesh=mesh,
                                axis_name=axis_name, controller=controller,
                                cost=cost)
        elif engine == "sharded":
            result = _run_sharded(problem, key, opts, mesh=mesh,
                                  data_axis=axis_name, pod_axis=pod_axis,
                                  controller=controller, cost=cost)
        elif engine == "sharded2d":
            result = _run_sharded(problem, key, opts, mesh=mesh,
                                  data_axis=data_axis,
                                  model_axis=model_axis,
                                  pod_axis=pod_axis,
                                  controller=controller, cost=cost)
        else:
            result = _run_reference(problem, key, opts,
                                    controller=controller, cost=cost)
    if journal is not None:
        from .obs.journal import write_run_journal
        if scenario is None:
            scenario = getattr(cost, "name", None)
        write_run_journal(journal, result, engine=engine, options=opts,
                          mesh=mesh, problem=problem, scenario=scenario)
    return result


def lower(problem, key, *, engine: str = "sharded",
          options: RanlOptions | None = None, mesh=None,
          axis_name: str = "data", data_axis: str = "data",
          model_axis: str = "model", pod_axis: str = "pod",
          controller=None, cost=None, **overrides):
    """Lower (without running) a sharded engine's program.

    Returns the ``jax.stages.Lowered`` for exactly the computation
    ``repro.run`` would execute with the same arguments;
    ``.compile().as_text()`` is the partitioned HLO that
    ``launch.hlo_analysis`` inventories (the one-param-sized-psum-per-
    round and peak-buffer assertions — quorum and overlap runs included).
    Only ``"sharded"`` and ``"sharded2d"`` have a lowering surface.
    """
    if engine not in _MESH_REQUIRED:
        raise ValueError(f"engine {engine!r} has no lowering surface — "
                         f"repro.lower supports {_MESH_REQUIRED}")
    opts, controller = _resolve(engine, options, mesh, controller,
                                overrides)
    with span("ranl.lower", engine=engine):
        if engine == "sharded":
            return _lower_sharded(problem, key, opts, mesh=mesh,
                                  data_axis=axis_name, pod_axis=pod_axis,
                                  controller=controller, cost=cost)
        return _lower_sharded(problem, key, opts, mesh=mesh,
                              data_axis=data_axis, model_axis=model_axis,
                              pod_axis=pod_axis, controller=controller,
                              cost=cost)


def trace(problem, key, *, engine: str = "scan",
          options: RanlOptions | None = None, mesh=None,
          axis_name: str = "data", data_axis: str = "data",
          model_axis: str = "model", pod_axis: str = "pod",
          controller=None, cost=None, **overrides):
    """Trace (without running) any engine's FULL program to a closed
    jaxpr — init phase and round loop.

    The pre-compile companion of ``repro.lower``: works for all five
    engines (the eager reference oracle included — its loop is a pure
    array program), with the same validation as ``repro.run``.  The
    result feeds ``repro.analysis.jaxpr_audit.audit_jaxpr`` (collective
    inventory with exact scan trip counts, PRNG key-reuse, dtype-leak
    and host-sync checks) and the ``repro.analysis.audit`` CLI's
    contract diffing.
    """
    opts, controller = _resolve(engine, options, mesh, controller,
                                overrides)
    return trace_ranl(problem, key, opts, engine=engine, mesh=mesh,
                      axis_name=axis_name, data_axis=data_axis,
                      model_axis=model_axis, pod_axis=pod_axis,
                      controller=controller, cost=cost)
