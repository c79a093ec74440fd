"""RANL driver — faithful implementation of Algorithm 1, compiled.

Round 0 (init): workers send stochastic local gradients and Hessians at x⁰;
the server aggregates H = mean ∇²F_i(x⁰, ξ⁰), projects [H]_μ (Definition 4),
seeds the memory C_i^{0,q} = ∇F_i^q(x⁰, ξ⁰), and takes one unpruned Newton
step.  Rounds t ≥ 1: workers draw masks m_i^t ~ P, train pruned sub-models
x_i = x ⊙ m_i, send pruned gradients; the server aggregates per region with
memory fallback and updates x^{t+1} = x^t − [H]_μ^{-1} ∇F^t.

Engine layout:

* the init-phase worker Hessian/gradient evaluations are ``vmap``-ed over
  workers instead of a host loop, and the Cholesky factor of [H]_μ is
  computed once (not re-factored every round);
* the round loop is a single ``jax.lax.scan`` — mask sampling, the pruned
  gradients (the problem's ``pruned_grads``: a ``vmap`` of its oracle, or
  for dense logistic regression one Pallas pass over each worker's X),
  server aggregation, and the projected-Newton step all live in the
  scanned body, so all rounds trace and compile once;
* coverage / communication / τ* diagnostics ride the scan outputs instead
  of host-side Python accumulators;
* the ``batch`` engine vmaps init + rounds over seeds: many independent
  runs in one compilation, for variance-banded convergence curves — and
  shards the seed axis across devices when given a ``mesh``;
* ``curvature="diag"`` swaps the dense Definition-4 eigen-projection for a
  Hutchinson diagonal estimate and dispatches each round's fused
  aggregate + projected-Newton step to the Pallas ``ranl_update`` kernel
  (interpret mode on CPU, compiled on TPU);
* the ``sharded`` and ``sharded2d`` engines are ONE round loop under
  ``shard_map`` (``_sharded_rounds_body``), given a placement.  The
  *worker* axis always partitions over ``"data"``: per-worker gradients
  and the gradient memory C_i stay device-local (the paper's per-worker
  state), and server aggregation is expressed as real collectives — a
  tiny region-sized ``psum`` for coverage counts plus exactly ONE
  param-sized ``psum`` per round (the single-reduction form of
  ``masked_aggregate``).  ``sharded2d`` adds the *dimension* axis
  ``"model"``: per-device row slices of C, G, hdiag and the region
  masks, the param all-reduce shrunk to a d/n_model-float psum over only
  the data axis, and (dense path) the replicated Cholesky replaced by a
  blocked right-looking factorization + blocked triangular solves over
  row panels.  The 2-D dense INIT is sharded too: the mean worker
  Hessian is accumulated as model-axis row panels
  (``worker_hessian_rows`` oracles, scan over local workers), the
  Definition-4 projection runs as the matmul-only Newton–Schulz
  iteration over those panels (``hessian.project_psd_ns_panels`` — no
  eigh, no replicated buffer), and the blocked factorization + first
  Newton step complete the phase, so with ``curvature="dense"`` NO
  device ever materializes a d×d buffer at ANY phase — init included,
  proven on the compiled HLO via ``hlo_analysis.max_array_bytes``.
  ``_lower_sharded`` (``repro.lower``) exposes the partitioned HLO for
  the memory/communication assertions;
* the sharded loop takes ``overlap=True``: a double-buffered
  (software-pipelined) round loop in which each round's param-shard
  ``psum`` is issued and, while it is in flight, the NEXT round's
  x-independent work — mask/key sampling and its coverage-count psum —
  plus this round's memory update and diagnostics are computed, the psum
  result being consumed only by the final Newton step.  Identical math
  (same values, same reductions), so parity with the sequential loop is
  exact; the restructure is what lets the XLA latency-hiding scheduler
  turn the all-reduce into an async start/done pair that hides behind
  compute on real links.

For single runs the init phase executes eagerly (op-by-op, exactly the
reference sequence) so the trajectory reproduces the ``reference`` engine
— the original host-loop driver kept below as the semantic oracle — on a
fixed key; parity tests pin this.  ``projection="ns"`` swaps the init
eigh for the same Newton–Schulz projection the 2-D engine shards — the
single-device oracle the 2-D dense parity tests compare against.

Closed-loop heterogeneity (``repro.hetero``): every engine takes
``controller=`` (a telemetry-driven mask allocator; ``policy=`` is
wrapped in the bit-exact ``PolicyController`` shim when absent) and
``cost=`` (a per-worker ``CostModel``; availability dynamics filter the
sampled masks, and the simulated per-round wall-clock / max-staleness
traces land in ``RanlResult.round_time`` / ``.max_stale``).  Controller
state and the telemetry ride the round loop's ``lax.scan`` carry in every
engine; in the sharded loop the controller runs replicated on
the full (N, Q) telemetry — it adds NO collective, the coverage-count
psum it observes is the one the aggregation already paid, so the
one-param-sized-psum-per-round HLO invariant is preserved with
controller state in the carry (pinned in tests).

Semi-synchronous rounds (``RanlOptions.quorum``; ``QuorumSpec`` in the
engines' static args): the round commits at the quorum deadline
(``hetero.cost.quorum_split`` — the k-th order statistic of worker times
instead of the max), only ON-TIME workers aggregate fresh, and late
contributions fold into later rounds with ``gamma**s`` damping through a
bounded ``(max_delay, d)`` late buffer that RIDES THE SCAN CARRY (the
sharded engines carry its device-local column slice and fold it inside
the round's one existing param-sized psum — the quorum path adds no
collective; the split itself is computed replicated from the full mask,
like the controller).  ``quorum=None`` compiles the historical
synchronous computation unchanged; ``quorum=1.0`` runs the quorum code
path but degenerates to it bit-exactly.

The engine internals are the ``_run_*`` functions taking ``RanlOptions``;
``repro.run``/``repro.lower`` (``repro.api``) dispatch to them.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dc_replace

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import span
from .aggregation import late_fold_updates, quorum_aggregate, \
    server_aggregate
from .compression import CompressionSpec, compressed_quorum_aggregate, \
    compressed_server_aggregate, lowrank_hmu_factor, parse_compression, \
    pod_sum_compressed, psum_compressed, uplink_bytes
from .hessian import hutchinson_diag, project_diag, project_psd, \
    project_psd_ns, project_psd_ns_panels, running_mean_hessian, \
    solve_projected
from .options import HierarchySpec, QuorumSpec, RanlOptions
from .regions import contiguous_regions, expand_mask, region_sizes


@dataclass
class RanlResult:
    xs: jnp.ndarray            # (T+2, d) iterates (x⁰ is row 0 ... x^{T+1})
    dist_sq: jnp.ndarray       # (T+2,) E‖x^t − x*‖² proxy (single run)
    losses: jnp.ndarray        # (T+2,)
    coverage: jnp.ndarray      # (T,) fraction of regions covered per round
    comm_floats: jnp.ndarray   # (T,) uplink floats actually transmitted
    tau_star: int              # realized min worker coverage over
                               # rounds/regions — 0 if ANY region went
                               # uncovered in any round (the quantity
                               # Theorem 1 is conditioned on).
                               # ((B,) array for batched runs)
    tau_covered: int = 0       # min coverage over COVERED regions only —
                               # the memory-fallback reading, where an
                               # uncovered region is served from C and does
                               # not count against fresh-gradient coverage.
                               # N when every region was always covered.
    round_time: jnp.ndarray = None   # (T,) simulated wall-clock per round
                               # (max over participating workers of
                               # compute+comm under the run's CostModel;
                               # kept-coordinate counts when none given)
    max_stale: jnp.ndarray = None    # (T,) max region staleness after each
                               # round (rounds since last covered)
    comm_bytes: jnp.ndarray = None   # (T,) modeled uplink BYTES actually
                               # transmitted per round (the
                               # core.compression wire model;
                               # 4 · comm_floats when uncompressed)
    pod_bytes: jnp.ndarray = None    # (T,) modeled INTER-POD bytes per
                               # round: hierarchical runs meter their
                               # exchange wire (attributed to each
                               # window's last round), flat runs on a
                               # pod topology (cost.pod_bw set) pay the
                               # param aggregate's crossing every round
    xs_pods: jnp.ndarray = None      # (T+2, P, d) pod-resolved iterates of
                               # a hierarchical run (``xs`` is their pod
                               # mean — the consensus estimate); None
                               # for flat runs
    grad_path: str = "vmap"    # how the round loop computed the workers'
                               # gradients: "fused" (one Pallas pass over
                               # each worker's data, the problem's
                               # ``pruned_grads``) or "vmap" (its
                               # ``worker_grad`` per worker)


def _init_phase(problem, k_init, *, mu: float, lr: float, curvature: str,
                hutch_samples: int, projection: str = "eigh",
                ns_iters: int = 60, hessian_rank: int | None = None):
    """Alg. 1 lines 1–8, worker evaluations vmapped/scanned.

    Returns (x1, C0, cho_c, cho_lower, hdiag): the post-init iterate, the
    seeded gradient memory, and the curvature state — a Cholesky factor of
    [H]_μ for the dense path, a projected diagonal estimate for the diag
    path (the unused one is None).  ``projection`` picks the Definition-4
    implementation on the dense path: ``"eigh"`` (the paper-literal
    eigenvalue clamp, and the reference-parity default) or ``"ns"`` (the
    matmul-only Newton–Schulz form — the single-device oracle of the
    dimension-sharded init).
    """
    with span("ranl.init"):
        N, d = problem.num_workers, problem.dim
        worker_ids = jnp.arange(N)
        grad_at = jax.vmap(problem.worker_grad, in_axes=(0, None, 0))

        x0 = jnp.zeros(d)
        hkeys = jax.random.split(jax.random.fold_in(k_init, 0), N)
        gkeys = jax.random.split(jax.random.fold_in(k_init, 1), N)
        with span("ranl.init.grad"):
            g0 = grad_at(worker_ids, x0, gkeys)          # (N, d)

        if curvature == "dense" and hessian_rank is not None:
            # compressed init exchange: project worker 0's Hessian once,
            # fold only the top-r eigenpairs of every other worker's
            # curvature via Cholesky rank-1 updates — no mean-Hessian
            # re-projection (see compression.lowrank_hmu_factor for the
            # exactness regime)
            with span("ranl.init.hessian"):
                cho_c, cho_lower = lowrank_hmu_factor(
                    problem, x0, hkeys, mu, rank=hessian_rank), True
            hdiag = None
            with span("ranl.init.factor"):
                step0 = jax.scipy.linalg.cho_solve((cho_c, cho_lower),
                                                   g0.mean(axis=0))
        elif curvature == "dense":
            # O(d²)-peak shared fold (see running_mean_hessian: the eager
            # left-to-right order is what keeps reference parity
            # bit-tight; the sharded2d dense init, whose oracle tolerance
            # is 1e-5, uses lax.scan for its panel accumulation instead).
            with span("ranl.init.hessian"):
                H = running_mean_hessian(problem, x0, hkeys)
            with span("ranl.init.project"):
                if projection == "ns":
                    h_mu = project_psd_ns(H, mu, num_iters=ns_iters)
                else:
                    h_mu = project_psd(H, mu)
            with span("ranl.init.factor"):
                cho_c, cho_lower = jax.scipy.linalg.cho_factor(h_mu)
                step0 = jax.scipy.linalg.cho_solve((cho_c, cho_lower),
                                                   g0.mean(axis=0))
            hdiag = None
        elif curvature == "diag":
            # Scalable path: Hutchinson diagonal of the mean worker Hessian
            # at x⁰ (Rademacher probes, HVPs through the gradient oracle);
            # the per-round step then only needs max(h, μ) — the diagonal
            # specialization of [·]_μ.
            def mean_grad(xx):
                return grad_at(worker_ids, xx, gkeys).mean(axis=0)

            with span("ranl.init.hessian"):
                hdiag = hutchinson_diag(mean_grad, x0,
                                        jax.random.fold_in(k_init, 2),
                                        num_samples=hutch_samples)
            cho_c, cho_lower = None, False
            with span("ranl.init.factor"):
                step0 = g0.mean(axis=0) / project_diag(hdiag, mu)
        else:
            raise ValueError(f"unknown curvature {curvature!r}")

        x1 = x0 - lr * step0
    return x1, g0, cho_c, cho_lower, hdiag


def _round_diagnostics(covered_q, count_q, n_workers: int):
    """Per-round (coverage_mean, min_count, min_covered_count).

    ``min_count`` is the raw count minimum, so an uncovered region
    contributes its literal 0 — it feeds ``tau_star``, the realized
    minimum the convergence theorem is conditioned on (the old mapping of
    uncovered regions to N hid them behind tau_star >= 1).
    ``min_covered_count`` maps uncovered regions to N (excluded from the
    min) — it feeds ``tau_covered``, the memory-fallback reading.  Single
    source of truth for every engine (scan/batch, sharded, reference).
    """
    return (covered_q.mean(), count_q.min(),
            jnp.where(covered_q, count_q, n_workers).min())


def _tau_pair(min_counts, min_cov_counts, n_workers: int):
    """Cap the over-rounds mins at N -> (tau_star, tau_covered)."""
    n_cap = jnp.asarray(n_workers, min_counts.dtype)
    return (jnp.minimum(n_cap, min_counts.min()),
            jnp.minimum(n_cap, min_cov_counts.min()))


def _controller_mask(controller, cost, ctrl_state, telem, kt, t,
                     num_workers: int, num_regions: int):
    """One controller step + the cost model's availability filter.

    Shared by every engine (scan/batch, sharded, reference).  The
    availability branch is STATIC (cost metadata), so a cost model
    without dropout/churn adds no ops and no PRNG use — the
    PolicyController default path stays bit-identical to the historical
    ``sample_masks`` call.
    """
    from ..hetero.cost import available
    M, ctrl_state = controller.step(ctrl_state, telem, kt, t,
                                    num_workers, num_regions)
    if cost.dropout_prob > 0.0 or cost.churn_period > 0:
        M = jnp.logical_and(M, available(cost, kt, t)[:, None])
    return M, ctrl_state


def _observe_round(cost, telem, M_full, count_q, sizes_q, t, ubytes=None):
    """Fold one round's observations into the telemetry carry.

    ``M_full``: the round's FULL (N, Q) mask (replicated in the sharded
    engines — per-worker work needs every row); ``count_q``: the (Q,)
    coverage counts the aggregation already computed; ``ubytes``: the
    per-worker uplink bytes of the round's (possibly compressed) wire
    model (None = the uncompressed 4 bytes/coordinate).  Returns the new
    telemetry, whose ``times``/``stale_q`` feed the per-round wall-clock
    and max-staleness traces.
    """
    from ..hetero.cost import worker_times
    from ..hetero.controller import next_telemetry
    work = (M_full * sizes_q[None, :]).sum(axis=1)
    times = worker_times(cost, work, t, ubytes)
    return next_telemetry(telem, count_q, work, times)


def _hetero_defaults(problem, policy, controller, cost):
    """Resolve (controller, cost): wrap a PolicyConfig in the bit-exact
    shim when no controller is given; default to the uniform cost model."""
    from ..hetero.controller import as_controller
    from ..hetero.cost import uniform_cost
    ctrl = as_controller(policy if controller is None else controller)
    if cost is None:
        cost = uniform_cost(problem.num_workers)
    return ctrl, cost


def _pod_wire_bytes(comp: CompressionSpec | None, n_coords: int) -> float:
    """Modeled bytes for an ``n_coords``-float payload crossing the
    inter-pod links under the ``core.compression`` wire model (int8: one
    byte per coordinate plus the 4-byte shared scale; bf16: two;
    uncompressed/topk: four) — the single source of
    ``RanlResult.pod_bytes`` and the ``pod_exchange_time`` charge."""
    if comp is None:
        return 4.0 * n_coords
    if comp.kind == "int8":
        return float(n_coords) + 4.0
    if comp.kind == "bf16":
        return 2.0 * n_coords
    return 4.0 * n_coords


def _check_hier(problem, hspec: HierarchySpec | None, num_rounds: int):
    """Dispatch-time divisibility checks shared by every engine."""
    if hspec is None:
        return
    if problem.num_workers % hspec.pods:
        raise ValueError(
            f"num_workers={problem.num_workers} must divide evenly "
            f"across hierarchy pods={hspec.pods}")
    if num_rounds > 0 and num_rounds % hspec.period:
        raise ValueError(
            f"num_rounds={num_rounds} must be a multiple of the "
            f"hierarchy exchange period={hspec.period}")


_ROUND_STATIC = ("num_rounds", "num_regions", "controller", "mu", "lr",
                 "curvature", "use_kernel", "interpret", "cho_lower",
                 "qspec", "comp", "hspec")


def _scan_rounds(problem, k_loop, x1, C0, cho_c, hdiag, cost, *,
                 num_rounds: int, num_regions: int, controller, mu: float,
                 lr: float, curvature: str, use_kernel: bool,
                 interpret: bool | None, cho_lower: bool,
                 qspec: QuorumSpec | None = None,
                 comp: CompressionSpec | None = None,
                 hspec: HierarchySpec | None = None):
    """Alg. 1 lines 9–23 as one ``lax.scan``; returns the full result set
    (xs, dist_sq, losses, coverage, comm, tau, times, stale) as arrays.

    The scan carry holds (x, C, late buffer, controller state, telemetry):
    the controller observes round t−1's coverage counts, per-worker
    simulated times and staleness counters when allocating round t's mask.
    With ``qspec`` set, rounds are semi-synchronous: the quorum deadline
    replaces the max in the round-time trace, only on-time workers
    aggregate fresh (the controller and the coverage/staleness
    diagnostics see ON-TIME counts), and the ``(max_delay, d)`` late
    buffer carries the ``gamma**s``-damped contributions of late workers
    forward (``quorum_aggregate``).  ``qspec=None`` is a static branch —
    the synchronous loop compiles unchanged (no buffer, no split).  The
    fused diag kernel has no late-fold form, so the quorum path always
    takes the jnp aggregation.

    ``comp`` switches on per-worker uplink compression with error
    feedback: the (N, d) residual rides the carry, the aggregation
    routes through ``compressed_server_aggregate`` /
    ``compressed_quorum_aggregate``, and the fused diag kernel is
    bypassed (it has no EF form).  ``comp=None`` is a static branch —
    the uncompressed loop compiles unchanged (no residual in the
    carry), which is the bit-exactness rail the tests pin.

    ``hspec`` switches on hierarchical pod-of-pods rounds (a separate
    loop — see ``_hier_scan_rounds``); ``hspec=None`` compiles the flat
    loop unchanged, except that a cost model with an attached pod
    topology (``cost.pod_bw`` — a static pytree branch) charges every
    flat round the param aggregate's inter-pod crossing.
    """
    from ..hetero.controller import initial_telemetry, next_telemetry
    from ..hetero.cost import pod_exchange_time, quorum_split, worker_times
    if hspec is not None and num_rounds > 0:
        return _hier_scan_rounds(
            problem, k_loop, x1, C0, cho_c, hdiag, cost,
            num_rounds=num_rounds, num_regions=num_regions,
            controller=controller, mu=mu, lr=lr, curvature=curvature,
            use_kernel=use_kernel, interpret=interpret,
            cho_lower=cho_lower, qspec=qspec, comp=comp, hspec=hspec)
    N, d = problem.num_workers, problem.dim
    Q = num_regions
    region_ids = contiguous_regions(d, Q)
    sizes_q = region_sizes(region_ids, Q)
    pod_wire = _pod_wire_bytes(comp, d)

    def body(carry, t):
        x, C, err, late_buf, ctrl_state, telem = carry
        kt = jax.random.fold_in(k_loop, t)
        M, ctrl_state = _controller_mask(controller, cost, ctrl_state,
                                         telem, kt, t, N, Q)  # (N, Q) bool
        Mx = expand_mask(M, region_ids)                  # (N, d) bool
        x_pruned = jnp.where(Mx, x[None, :], 0.0)        # x ⊙ m_i
        gk = jax.random.split(jax.random.fold_in(kt, 7), N)
        G = problem.pruned_grads(x_pruned, gk, use_kernel=use_kernel,
                                 interpret=interpret) * Mx  # ∇F_i ⊙ m_i
        ubytes = uplink_bytes(comp, M, sizes_q)          # (N,) wire model
        if qspec is not None:
            work = (M * sizes_q[None, :]).sum(axis=1)
            times = worker_times(cost, work, t, ubytes)
            deadline, on_time, delays = quorum_split(
                times, M, quorum=qspec.quorum, quorum_tau=qspec.quorum_tau,
                max_delay=qspec.max_delay)
            if comp is None:
                g, C, late_buf = quorum_aggregate(
                    G, Mx, C, on_time, delays, late_buf, gamma=qspec.gamma,
                    max_delay=qspec.max_delay)
            else:
                g, C, err, late_buf = compressed_quorum_aggregate(
                    G, Mx, C, err, on_time, delays, late_buf, comp,
                    region_ids=region_ids, num_regions=Q,
                    gamma=qspec.gamma, max_delay=qspec.max_delay)
            if curvature == "dense":
                step = jax.scipy.linalg.cho_solve((cho_c, cho_lower), g)
            else:
                step = g / project_diag(hdiag, mu)
            x = x - lr * step
            count_q = (M & on_time[:, None]).sum(axis=0)  # on-time counts
            telem = next_telemetry(telem, count_q, work, times)
            round_t = deadline
        elif curvature == "diag" and use_kernel and comp is None:
            from ..kernels.region_aggregate import ranl_update
            # interpret=None lets the kernel layer pick the dispatch mode
            # (interpret off-TPU, compiled on TPU) — single source of truth
            x, C = ranl_update(x, hdiag, G, Mx, C, mu=mu, lr=lr,
                               interpret=interpret)
        else:
            if comp is None:
                g, C = server_aggregate(G, Mx, C)
            else:
                g, C, err = compressed_server_aggregate(
                    G, Mx, C, err, comp, region_ids=region_ids,
                    num_regions=Q)
            if curvature == "dense":
                step = jax.scipy.linalg.cho_solve((cho_c, cho_lower), g)
            else:
                step = g / project_diag(hdiag, mu)
            x = x - lr * step
        if qspec is None:
            count_q = M.sum(axis=0)
            telem = _observe_round(cost, telem, M, count_q, sizes_q, t,
                                   ubytes)
            round_t = telem.times.max()
        if cost.pod_bw is not None:
            # flat rounds on a pod topology: the param aggregate crosses
            # every inter-pod link every round
            round_t = round_t + pod_exchange_time(cost, pod_wire)
            pb = jnp.float32(pod_wire)
        else:
            pb = jnp.float32(0.0)
        cov_mean, min_count, min_cov_count = _round_diagnostics(
            count_q > 0, count_q, N)
        return (x, C, err, late_buf, ctrl_state, telem), (
            x, cov_mean, Mx.sum(), min_count, min_cov_count,
            round_t, telem.stale_q.max(), ubytes.sum(), pb)

    x0 = jnp.zeros(d)
    late_buf0 = (() if qspec is None
                 else jnp.zeros((qspec.max_delay, d)))
    err0 = (() if comp is None else jnp.zeros((N, d)))
    if num_rounds > 0:
        ts = jnp.arange(1, num_rounds + 1)
        carry0 = (x1, C0, err0, late_buf0, controller.init_state(N, Q),
                  initial_telemetry(N, Q))
        _, (xs_t, cov, comm, min_counts, min_cov_counts, times,
            stale, cbytes, pbytes) = jax.lax.scan(body, carry0, ts)
        xs = jnp.concatenate([jnp.stack([x0, x1]), xs_t], axis=0)
        tau, tau_cov = _tau_pair(min_counts, min_cov_counts, N)
    else:
        xs = jnp.stack([x0, x1])
        cov = jnp.zeros((0,))
        comm = jnp.zeros((0,), jnp.int32)
        tau = jnp.asarray(N, jnp.int32)
        tau_cov = jnp.asarray(N, jnp.int32)
        times = jnp.zeros((0,))
        stale = jnp.zeros((0,), jnp.int32)
        cbytes = jnp.zeros((0,))
        pbytes = jnp.zeros((0,))

    dist = jnp.sum((xs - problem.x_star[None, :]) ** 2, axis=1)
    losses = jax.vmap(problem.loss)(xs)
    return (xs, dist, losses, cov, comm, tau, tau_cov, times, stale,
            cbytes, pbytes)


def _hier_scan_rounds(problem, k_loop, x1, C0, cho_c, hdiag, cost, *,
                      num_rounds: int, num_regions: int, controller,
                      mu: float, lr: float, curvature: str,
                      use_kernel: bool, interpret: bool | None,
                      cho_lower: bool, qspec: QuorumSpec | None,
                      comp: CompressionSpec | None, hspec: HierarchySpec):
    """Hierarchical pod-of-pods rounds in one program (scan engine).

    The worker axis splits into ``hspec.pods`` contiguous pods; each pod
    runs the EXACT flat round math on its own sub-population — pod-local
    coverage counts and denominators, pod-local memory fallback ``C/N_p``
    (the per-pod ``vmap`` of ``server_aggregate`` and the quorum/
    compression aggregators gives this for free), pod-local quorum
    deadlines — against its own iterate ``x_p``.  Every ``period``
    rounds the pods exchange anchored deltas and damp toward consensus:

        Δ_p = x_p − anchor;  x̄ = anchor + (Σ_p Δ_p) / P
        x_p += γ · (x̄ − x_p);  anchor = x̄

    (``anchor`` starts at the replicated post-init iterate, so the first
    exchange's deltas are exactly the accumulated pod drift).  The
    anchored-delta form is what the optional int8/bf16 exchange
    compression quantizes — small when pods agree — with its own
    error-feedback residual in the OUTER carry
    (``pod_sum_compressed``, bit-matching the sharded engines'
    ``psum_compressed`` over the pod mesh axis).  The loop is a nested
    scan — outer over the ``num_rounds/period`` exchange windows, inner
    over the window's rounds — which in the sharded engines is precisely
    what makes the pod-axis collective's HLO loop multiplier E =
    num_rounds/period instead of num_rounds: the
    inter-pod-bytes-shrink-by-period claim, proven on compiled HLO.

    ``pods=1`` degenerates to the flat trajectory (the parity rail);
    exchange wire bytes land in the ``pod_bytes`` trace on each window's
    last round, and ``pod_exchange_time`` joins that round's clock when
    the cost model carries a pod topology.  The fused diag kernel has no
    pod-resolved form, so this path always takes the jnp aggregation.
    Returns the 11-tuple of ``_scan_rounds`` with ``xs`` carrying an
    extra pod axis: (T+2, P, d) — the caller publishes the pod mean.
    """
    from ..hetero.controller import initial_telemetry, next_telemetry
    from ..hetero.cost import pod_exchange_time, quorum_split, worker_times
    N, d = problem.num_workers, problem.dim
    pods, period = hspec.pods, hspec.period
    n_pod = N // pods
    Q = num_regions
    region_ids = contiguous_regions(d, Q)
    sizes_q = region_sizes(region_ids, Q)
    hcomp = parse_compression(hspec.compression)
    pod_wire = _pod_wire_bytes(hcomp, d)

    def body(carry, t):
        x, C, err, late_buf, ctrl_state, telem = carry   # x: (P, d)
        kt = jax.random.fold_in(k_loop, t)
        M, ctrl_state = _controller_mask(controller, cost, ctrl_state,
                                         telem, kt, t, N, Q)
        Mx = expand_mask(M, region_ids)                  # (N, d) bool
        x_w = jnp.repeat(x, n_pod, axis=0)               # worker's pod iterate
        x_pruned = jnp.where(Mx, x_w, 0.0)
        gk = jax.random.split(jax.random.fold_in(kt, 7), N)
        G = problem.pruned_grads(x_pruned, gk, use_kernel=use_kernel,
                                 interpret=interpret) * Mx
        ubytes = uplink_bytes(comp, M, sizes_q)
        Gp = G.reshape(pods, n_pod, d)
        Mxp = Mx.reshape(pods, n_pod, d)
        Mp = M.reshape(pods, n_pod, Q)
        Cp = C.reshape(pods, n_pod, d)
        if qspec is not None:
            work = (M * sizes_q[None, :]).sum(axis=1)
            times = worker_times(cost, work, t, ubytes)
            split = functools.partial(
                quorum_split, quorum=qspec.quorum,
                quorum_tau=qspec.quorum_tau, max_delay=qspec.max_delay)
            deadline_p, on_p, delays_p = jax.vmap(split)(
                times.reshape(pods, n_pod), Mp)
            if comp is None:
                agg = functools.partial(quorum_aggregate,
                                        gamma=qspec.gamma,
                                        max_delay=qspec.max_delay)
                g_p, Cp, late_buf = jax.vmap(agg)(Gp, Mxp, Cp, on_p,
                                                  delays_p, late_buf)
            else:
                agg = functools.partial(compressed_quorum_aggregate,
                                        comp=comp, region_ids=region_ids,
                                        num_regions=Q, gamma=qspec.gamma,
                                        max_delay=qspec.max_delay)
                errp = err.reshape(pods, n_pod, d)
                g_p, Cp, errp, late_buf = jax.vmap(agg)(
                    Gp, Mxp, Cp, errp, on_p, delays_p, late_buf)
                err = errp.reshape(N, d)
            count_pq = (Mp & on_p[:, :, None]).sum(axis=1)   # (P, Q)
            telem = next_telemetry(telem, count_pq.sum(axis=0), work,
                                   times)
            round_t = deadline_p.max()
        else:
            if comp is None:
                g_p, Cp = jax.vmap(server_aggregate)(Gp, Mxp, Cp)
            else:
                agg = functools.partial(compressed_server_aggregate,
                                        comp=comp, region_ids=region_ids,
                                        num_regions=Q)
                errp = err.reshape(pods, n_pod, d)
                g_p, Cp, errp = jax.vmap(agg)(Gp, Mxp, Cp, errp)
                err = errp.reshape(N, d)
            count_pq = Mp.sum(axis=1)                        # (P, Q)
            telem = _observe_round(cost, telem, M, count_pq.sum(axis=0),
                                   sizes_q, t, ubytes)
            round_t = telem.times.max()
        C = Cp.reshape(N, d)
        if curvature == "dense":
            step = jax.vmap(
                lambda g: jax.scipy.linalg.cho_solve((cho_c, cho_lower),
                                                     g))(g_p)
        else:
            step = g_p / project_diag(hdiag, mu)[None, :]
        x = x - lr * step
        cov_mean, min_count, min_cov_count = _round_diagnostics(
            count_pq > 0, count_pq, n_pod)
        return (x, C, err, late_buf, ctrl_state, telem), (
            x, cov_mean, Mx.sum(), min_count, min_cov_count,
            round_t, telem.stale_q.max(), ubytes.sum(), jnp.float32(0.0))

    def window(ocarry, w):
        carry, anchor, err_pod = ocarry
        ts_w = w * period + jnp.arange(1, period + 1)
        carry, outs = jax.lax.scan(body, carry, ts_w)
        x = carry[0]
        delta = x - anchor[None, :]                      # (P, d)
        if hcomp is None:
            total = delta.sum(axis=0)
        else:
            total, err_pod = pod_sum_compressed(hcomp, delta, err_pod)
        xbar = anchor + total / pods
        x = x + hspec.gamma * (xbar[None, :] - x)
        ex_t = pod_exchange_time(cost, pod_wire)
        outs = (outs[:5] + (outs[5].at[-1].add(ex_t),) + outs[6:8]
                + (outs[8].at[-1].add(pod_wire),))
        return ((x,) + carry[1:], xbar, err_pod), outs

    x0 = jnp.zeros(d)
    late_buf0 = (() if qspec is None
                 else jnp.zeros((pods, qspec.max_delay, d)))
    err0 = (() if comp is None else jnp.zeros((N, d)))
    err_pod0 = (() if hcomp is None else jnp.zeros((pods, d)))
    carry0 = (jnp.tile(x1[None, :], (pods, 1)), C0, err0, late_buf0,
              controller.init_state(N, Q), initial_telemetry(N, Q))
    _, outs = jax.lax.scan(window, (carry0, x1, err_pod0),
                           jnp.arange(num_rounds // period))
    (xs_t, cov, comm, min_counts, min_cov_counts, times, stale, cbytes,
     pbytes) = jax.tree.map(
        lambda a: a.reshape((num_rounds,) + a.shape[2:]), outs)
    xs = jnp.concatenate(
        [jnp.stack([jnp.tile(x0[None, :], (pods, 1)),
                    jnp.tile(x1[None, :], (pods, 1))]), xs_t], axis=0)
    tau, tau_cov = _tau_pair(min_counts, min_cov_counts, n_pod)
    xbar_t = xs.mean(axis=1)                             # (T+2, d) consensus
    dist = jnp.sum((xbar_t - problem.x_star[None, :]) ** 2, axis=1)
    losses = jax.vmap(problem.loss)(xbar_t)
    return (xs, dist, losses, cov, comm, tau, tau_cov, times, stale,
            cbytes, pbytes)


_rounds_jit = functools.partial(
    jax.jit, static_argnames=_ROUND_STATIC)(_scan_rounds)

_BATCH_STATIC = ("num_rounds", "num_regions", "controller", "mu", "lr",
                 "curvature", "use_kernel", "interpret", "hutch_samples",
                 "projection", "ns_iters", "qspec", "comp", "hessian_rank",
                 "hspec")


def _ranl_batch_engine(problem, keys, cost, *, num_rounds, num_regions,
                       controller, mu, lr, curvature, use_kernel,
                       interpret, hutch_samples, projection, ns_iters,
                       qspec=None, comp=None, hessian_rank=None,
                       hspec=None):
    def one(key):
        k_init, k_loop = jax.random.split(key)
        x1, C0, cho_c, cho_lower, hdiag = _init_phase(
            problem, k_init, mu=mu, lr=lr, curvature=curvature,
            hutch_samples=hutch_samples, projection=projection,
            ns_iters=ns_iters, hessian_rank=hessian_rank)
        return _scan_rounds(problem, k_loop, x1, C0, cho_c, hdiag, cost,
                            num_rounds=num_rounds, num_regions=num_regions,
                            controller=controller, mu=mu, lr=lr,
                            curvature=curvature, use_kernel=use_kernel,
                            interpret=interpret, cho_lower=cho_lower,
                            qspec=qspec, comp=comp, hspec=hspec)
    return jax.vmap(one)(keys)


_batch_jit = functools.partial(
    jax.jit, static_argnames=_BATCH_STATIC)(_ranl_batch_engine)


# --------------------------------------------------------------------------
# sharded engines: one round loop under shard_map, given a placement — the
# worker axis over ``data_axis`` (jointly ``(pod_axis, data_axis)`` when
# hierarchical) and, when ``model_axis`` is set, the dimension d over it
# --------------------------------------------------------------------------

def _replicated_specs(tree):
    return jax.tree.map(lambda l: P(*([None] * jnp.ndim(l))), tree)


def _worker_sharded_specs(problem, axis_name: str):
    """Shard every worker-indexed problem leaf (leading dim == N, ndim >= 2
    in both problem classes) over ``axis_name``; replicate the rest."""
    N = problem.num_workers

    def spec(leaf):
        if leaf.ndim >= 2 and leaf.shape[0] == N:
            return P(axis_name, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(spec, problem)


def _sharded_rounds_body(problem, k_loop, x1, C0, chol, hdiag, cost, *,
                         data_axis: str, model_axis: str | None,
                         num_rounds: int, num_regions: int, controller,
                         mu: float, lr: float, curvature: str,
                         use_kernel: bool, interpret: bool | None,
                         cho_lower: bool, num_workers: int, n_data: int,
                         n_model: int, overlap: bool,
                         qspec: QuorumSpec | None = None,
                         comp: CompressionSpec | None = None,
                         pod_axis: str = "pod",
                         hspec: HierarchySpec | None = None):
    """Per-device round loop of both sharded engines (runs under
    ``shard_map``; the 2-D dense program calls it inline after its sharded
    init).

    The placement: ``problem``/``C0`` arrive worker-sharded over
    ``data_axis`` (N/n_data local workers).  With ``model_axis=None`` each
    device holds all of d, and the curvature state is replicated: ``chol``
    with ``cho_lower`` (the Cholesky factor of [H]_μ) or ``hdiag``.  With a
    ``model_axis`` d splits too: C, the pruned gradients, ``hdiag`` and
    the region masks are the device's p = d/n_model rows, ``chol`` is its
    (p, d) row panel of the factor, and the dense solve is the blocked one
    over the panels.  ``x1`` is replicated in both (the gradient oracles
    need the full iterate).  Each round issues one region-sized psum
    (coverage counts) and exactly ONE param-sized psum over ``data_axis``
    (the single-reduction aggregate of p floats); the 2-D solve adds only
    model-axis collectives.  The memory C never leaves the device that
    owns its (worker, rows) tile.

    ``overlap=True`` software-pipelines the loop: round t's mask/key
    sampling and coverage-count psum move into iteration t−1's carry, so
    inside each iteration the param-sized psum is issued right after the
    local gradient compute and its result is consumed only by the final
    solve — everything in between (next round's sampling + count psum,
    the memory update, diagnostics) is independent work the scheduler can
    run while the all-reduce is in flight.  Same values, same reductions:
    the trajectory is identical to the sequential loop.

    The controller runs REPLICATED: every device steps it on the full
    (N, Q) telemetry (tiny state, deterministic — all devices agree),
    exactly like the full-mask sampling below, so closing the loop adds
    no collective and the one-param-sized-psum-per-round invariant
    survives with controller state and telemetry in the carry.

    With ``qspec`` the round is semi-synchronous: the quorum split
    (deadline, on-time workers, delays) is computed REPLICATED from the
    full mask and times in ``sample_round`` — x-independent, so it rides
    the overlap carry like the mask itself — and the device-local
    ``(max_delay, p)`` late-buffer tile folds into the round's ONE
    param-sized psum (each device contributes its own workers' damped
    late mass), so the quorum path adds NO collective.  ``qspec=None``
    compiles the synchronous loop unchanged.

    With ``comp`` the round's one param-sized psum carries a COMPRESSED
    payload (``psum_compressed``): the device's pre-reduction contribution
    — plus, in quorum mode, its due late-buffer row, since the late mass
    physically rides the same all-reduce on this wire — is quantized
    (int8 shared-scale / bf16) or top-k sparsified, with a per-device
    error-feedback residual ``err`` (p,) in the scan carry.  Top-k region
    selection is per model shard (the residual absorbs the difference).
    The memory C and the late buffer stay device-local and exact.
    ``comp=None`` is a static Python branch: the uncompressed loop
    compiles unchanged.

    With ``hspec`` the loop is hierarchical: workers shard JOINTLY over
    ``(pod_axis, data_axis)``, so every in-round collective — the count
    psum and the ONE param-sized psum — reduces over ``data_axis`` only
    (or ``model_axis``, for the 2-D assembly) and is therefore pod-local
    for free (pod-local coverage counts, denominators and ``C/N_p``
    fallback — the same round math each pod of the scan engine's
    ``_hier_scan_rounds`` runs).  The scan nests: outer over the
    ``num_rounds/period`` exchange windows, inner over each window's
    rounds, and the ONLY ``pod_axis`` collective in the whole loop is the
    anchored-delta exchange at the window tail — one d-sized psum
    (optionally int8/bf16-compressed with its own error-feedback
    residual) whose HLO loop multiplier is the window count E, not the
    round count T.  That nesting is the inter-pod-bytes-shrink-by-period
    contract the HLO auditor proves.
    """
    from ..hetero.cost import pod_exchange_time, quorum_split, worker_times
    from ..hetero.controller import initial_telemetry, next_telemetry
    N, Q = num_workers, num_regions
    d = x1.shape[0]
    p = d // n_model                      # rows of d held by this device
    n_local = problem.num_workers         # workers held by this shard
    me_d = jax.lax.axis_index(data_axis)
    split_d = model_axis is not None      # d split over model_axis
    me_m = jax.lax.axis_index(model_axis) if split_d else 0
    hier = hspec is not None
    pods = hspec.pods if hier else 1
    n_pop = N // pods                     # workers per pod (= N when flat)
    me_pod = jax.lax.axis_index(pod_axis) if hier else 0
    wstart = (me_pod * n_data + me_d) * n_local if hier else me_d * n_local
    row_start = me_m * p if split_d else 0
    region_ids = contiguous_regions(d, Q)
    if split_d:
        from ..kernels.region_aggregate import local_region_ids
        region_ids_loc = local_region_ids(d, Q, row_start, p)
        grad_local = jax.vmap(
            lambda i, xp, k: problem.worker_grad_rows(i, xp, k, row_start, p))
    else:
        region_ids_loc = region_ids
        grad_local = jax.vmap(problem.worker_grad, in_axes=(0, 0, 0))
    sizes_q = region_sizes(region_ids, Q)           # (Q,) static
    local_ids = jnp.arange(n_local)
    hcomp = parse_compression(hspec.compression) if hier else None
    pod_wire = _pod_wire_bytes(comp, d)   # flat-on-topology charge
    hier_wire = _pod_wire_bytes(hcomp, d)
    # the fused Pallas kernel aggregates over the workers it can see, so it
    # is exact only when this device sees ALL workers (pure model-parallel
    # meshes); otherwise the collective jnp form is used.  It has no
    # late-fold form, so quorum and hierarchical runs always take the jnp
    # path, and the ("data",) placement never takes it.
    kernel_ok = (split_d and use_kernel and curvature == "diag"
                 and n_data == 1 and qspec is None and comp is None
                 and not hier)

    def sample_round(t, ctrl_state, telem):
        """Everything x-independent about round t: step the controller on
        the FULL (N, Q) telemetry on every device (tiny, and it keeps the
        stream bit-identical to the single-device engine), slice out this
        shard's workers, reduce the coverage counts (Q ints), price the
        round under the cost model, and (quorum mode) split it at the
        quorum deadline.  Returns (sampled, ctrl_state) where ``sampled``
        ends in the round's quorum info — ``()`` when synchronous."""
        kt = jax.random.fold_in(k_loop, t)
        M_full, ctrl_state = _controller_mask(controller, cost, ctrl_state,
                                              telem, kt, t, N, Q)
        gk_full = jax.random.split(jax.random.fold_in(kt, 7), N)
        M = jax.lax.dynamic_slice_in_dim(M_full, wstart, n_local)
        gk = jax.lax.dynamic_slice_in_dim(gk_full, wstart, n_local)
        # pod-local counts under hier: same collective, per-pod values
        count_q = jax.lax.psum(M.sum(axis=0), data_axis)
        work = (M_full * sizes_q[None, :]).sum(axis=1)
        ubytes = uplink_bytes(comp, M_full, sizes_q)
        times = worker_times(cost, work, t, ubytes, overlap=overlap)
        if qspec is None:
            qinfo = ()
            # replicated display/telemetry counts (pod-resolved when hier)
            count_disp = (M_full.reshape(pods, n_pop, Q).sum(axis=1)
                          if hier else count_q)
        elif hier:
            split = functools.partial(
                quorum_split, quorum=qspec.quorum,
                quorum_tau=qspec.quorum_tau, max_delay=qspec.max_delay)
            deadline_p, on_p, delays_p = jax.vmap(split)(
                times.reshape(pods, n_pop), M_full.reshape(pods, n_pop, Q))
            count_disp = (M_full.reshape(pods, n_pop, Q)
                          & on_p[:, :, None]).sum(axis=1)        # (P, Q)
            count_on_loc = jax.lax.dynamic_slice_in_dim(
                count_disp, me_pod, 1)[0]                        # my pod's
            qinfo = (count_disp,
                     jax.lax.dynamic_slice_in_dim(on_p.reshape(N),
                                                  wstart, n_local),
                     jax.lax.dynamic_slice_in_dim(delays_p.reshape(N),
                                                  wstart, n_local),
                     deadline_p.max(), count_on_loc)
        else:
            deadline, on_time, delays = quorum_split(
                times, M_full, quorum=qspec.quorum,
                quorum_tau=qspec.quorum_tau, max_delay=qspec.max_delay)
            count_on = (M_full & on_time[:, None]).sum(axis=0)
            count_disp = count_on
            qinfo = (count_on,
                     jax.lax.dynamic_slice_in_dim(on_time, wstart, n_local),
                     jax.lax.dynamic_slice_in_dim(delays, wstart, n_local),
                     deadline, count_on)
        return (M, gk, count_q, work, times, qinfo, ubytes,
                count_disp), ctrl_state

    def scatter_rows(vec_loc):
        """Assemble a replicated (d,) vector from this device's rows — one
        model-axis psum of d floats; the identity when d is not split."""
        if not split_d:
            return vec_loc
        return jax.lax.psum(
            jax.lax.dynamic_update_slice(jnp.zeros(d, vec_loc.dtype),
                                         vec_loc, (row_start,)), model_axis)

    def _psum_payload(y, err):
        """The round's ONE param-sized all-reduce over ``data_axis`` —
        compressed when ``comp`` is set (returns the updated
        error-feedback residual)."""
        if comp is None:
            return jax.lax.psum(y, data_axis), err
        return psum_compressed(comp, y, err, axis_name=data_axis,
                               n_agg=n_data, region_ids=region_ids_loc,
                               num_regions=Q)

    def round_update(x, C, err, late_buf, sampled):
        """The x-dependent half, up to issuing the round's ONE param-sized
        all-reduce: pruned local gradients, then the single-reduction
        aggregation (masked_aggregate's form) — covered fresh-mean and
        uncovered memory-mean folded into one per-worker contribution, so
        the worker-axis sum is the round's only param-sized psum.  G is
        exactly zero outside each worker's mask, so no re-masking is
        needed.  Quorum mode: only on-time workers contribute fresh (over
        the FULL count, so late γ-damped arrivals reconstruct the
        synchronous mean), the device-local late buffer's due row joins
        the same psum, and this round's late work enqueues.

        Returns (x_new, C, err, g, late_buf): the fused kernel path
        returns the new iterate (its model-axis assembly psum issued) and
        ``g=None``; otherwise ``x_new=None`` and ``g`` is the psum's
        result for ``finish_step`` to consume."""
        M, gk, count_q, qinfo = sampled[0], sampled[1], sampled[2], sampled[5]
        Mx_full = expand_mask(M, region_ids)        # (n_local, d)
        Mx = (expand_mask(M, region_ids_loc) if split_d
              else Mx_full)                         # (n_local, p) own rows
        x_pruned = jnp.where(Mx_full, x[None, :], 0.0)
        G = grad_local(local_ids, x_pruned, gk) * Mx
        if kernel_ok:
            from ..kernels.region_aggregate import ranl_update
            # all workers are local: the fused aggregate + projected-Newton
            # kernel runs on this device's d-slice unchanged
            x_loc = jax.lax.dynamic_slice(x, (row_start,), (p,))
            x_loc, C = ranl_update(x_loc, hdiag, G, Mx, C, mu=mu, lr=lr,
                                   interpret=interpret)
            return scatter_rows(x_loc), C, err, None, late_buf
        count_x = jnp.take(count_q, region_ids_loc)
        denom = jnp.maximum(count_x, 1).astype(G.dtype)
        if qspec is None:
            covered_x = jnp.take(count_q > 0, region_ids_loc)
            contrib = jnp.where(covered_x[None, :], G / denom, C / n_pop)
            g, err = _psum_payload(contrib.sum(axis=0), err)
            C = jnp.where(Mx, G, C)                 # device-local
            return None, C, err, g, late_buf
        on_loc, delays_loc = qinfo[1], qinfo[2]
        covered_x = jnp.take(qinfo[4] > 0, region_ids_loc)  # my pod's
        fresh = jnp.where(on_loc[:, None], G, 0.0)
        contrib = jnp.where(covered_x[None, :], fresh / denom, C / n_pop)
        g, err = _psum_payload(contrib.sum(axis=0) + late_buf[0], err)
        adds = late_fold_updates(G, Mx, count_x.astype(G.dtype),
                                 delays_loc, gamma=qspec.gamma,
                                 max_delay=qspec.max_delay)
        late_buf = jnp.concatenate(
            [late_buf[1:], jnp.zeros_like(late_buf[:1])], axis=0) + adds
        dropped = delays_loc > qspec.max_delay
        C = jnp.where(Mx & ~dropped[:, None], G, C)
        return None, C, err, g, late_buf

    def finish_step(x, g):
        if curvature == "diag":
            step = scatter_rows(g / project_diag(hdiag, mu))
        elif split_d:
            step = _blocked_solve_panels(
                chol, g, model_axis=model_axis, n_model=n_model,
                me=me_m, row_start=row_start, dim=d)
        else:
            step = jax.scipy.linalg.cho_solve((chol, cho_lower), g)
        return x - lr * step

    def round_obs(sampled):
        """(telemetry count, round-time trace, inter-pod bytes) for this
        round — on-time counts and the quorum deadline in quorum mode.
        The telemetry count is always GLOBAL (Q,); the display counts in
        ``sampled[7]`` stay pod-resolved.  Flat rounds on a pod topology
        charge the param aggregate's inter-pod crossing here (hier
        rounds pay only at the window-tail exchange)."""
        times, qinfo, count_disp = sampled[4], sampled[5], sampled[7]
        telem_count = count_disp.sum(axis=0) if hier else count_disp
        round_t = times.max() if qspec is None else qinfo[3]
        if cost.pod_bw is not None and not hier:
            round_t = round_t + pod_exchange_time(cost, pod_wire)
            pb = jnp.float32(pod_wire)
        else:
            pb = jnp.float32(0.0)
        return telem_count, round_t, pb

    def diagnostics(sampled):
        # uplink floats, from the replicated full-mask work (no extra
        # psum); comm stays FULL coverage (late workers still transmit)
        # while the coverage/τ diagnostics see the displayed (on-time,
        # pod-resolved when hier) counts
        work, count_disp = sampled[3], sampled[7]
        comm = work.sum()
        cov_mean, min_count, min_cov_count = _round_diagnostics(
            count_disp > 0, count_disp, n_pop)
        return comm, cov_mean, min_count, min_cov_count

    ctrl_state0 = controller.init_state(N, Q)
    telem0 = initial_telemetry(N, Q)
    late_buf0 = (() if qspec is None
                 else jnp.zeros((qspec.max_delay, p)))
    err0 = (() if comp is None else jnp.zeros(p))
    if overlap:
        def body(carry, t):
            x, C, err, late_buf, ctrl_state, telem, sampled = carry
            x_new, C, err, g, late_buf = round_update(
                x, C, err, late_buf, sampled)                # psum issued
            # overlap window: fold round t's observations into the
            # telemetry, sample round t+1 (controller step + count psum),
            # and compute round t's diagnostics — none of it touches g
            count_obs, round_t, pb = round_obs(sampled)
            telem = next_telemetry(telem, count_obs, sampled[3],
                                   sampled[4])
            nxt, ctrl_state = sample_round(t + 1, ctrl_state, telem)
            comm, cov_mean, min_count, min_cov_count = diagnostics(sampled)
            if x_new is None:
                x_new = finish_step(x, g)     # first consumer of the psum
            return (x_new, C, err, late_buf, ctrl_state, telem, nxt), (
                x_new, cov_mean, comm, min_count, min_cov_count,
                round_t, telem.stale_q.max(), sampled[6].sum(), pb)

        nxt0, ctrl_state0 = sample_round(1, ctrl_state0, telem0)
        init_carry = (x1, C0, err0, late_buf0, ctrl_state0, telem0, nxt0)
    else:
        def body(carry, t):
            x, C, err, late_buf, ctrl_state, telem = carry
            sampled, ctrl_state = sample_round(t, ctrl_state, telem)
            x_new, C, err, g, late_buf = round_update(
                x, C, err, late_buf, sampled)
            if x_new is None:
                x_new = finish_step(x, g)
            count_obs, round_t, pb = round_obs(sampled)
            telem = next_telemetry(telem, count_obs, sampled[3],
                                   sampled[4])
            comm, cov_mean, min_count, min_cov_count = diagnostics(sampled)
            return (x_new, C, err, late_buf, ctrl_state, telem), (
                x_new, cov_mean, comm, min_count, min_cov_count,
                round_t, telem.stale_q.max(), sampled[6].sum(), pb)

        init_carry = (x1, C0, err0, late_buf0, ctrl_state0, telem0)

    if not hier:
        ts = jnp.arange(1, num_rounds + 1)
        _, outs = jax.lax.scan(body, init_carry, ts)
    else:
        def window(ocarry, w):
            """One exchange window: ``period`` pod-local rounds, then the
            single pod-axis collective of the loop — the anchored-delta
            exchange on the replicated iterate (see ``_hier_scan_rounds``
            for the math)."""
            carry, anchor, err_pod = ocarry
            ts_w = w * period + jnp.arange(1, period + 1)
            carry, outs = jax.lax.scan(body, carry, ts_w)
            x = carry[0]
            delta = x - anchor
            if hcomp is None:
                total = jax.lax.psum(delta, pod_axis)
            else:
                total, err_pod = psum_compressed(
                    hcomp, delta, err_pod, axis_name=pod_axis,
                    n_agg=pods, region_ids=region_ids, num_regions=Q)
            xbar = anchor + total / pods
            x = x + hspec.gamma * (xbar - x)
            ex_t = pod_exchange_time(cost, hier_wire)
            outs = (outs[:5] + (outs[5].at[-1].add(ex_t),) + outs[6:8]
                    + (outs[8].at[-1].add(hier_wire),))
            return ((x,) + carry[1:], xbar, err_pod), outs

        period = hspec.period
        err_pod0 = () if hcomp is None else jnp.zeros(d)
        _, outs = jax.lax.scan(window, (init_carry, x1, err_pod0),
                               jnp.arange(num_rounds // period))
        outs = jax.tree.map(
            lambda a: a.reshape((num_rounds,) + a.shape[2:]), outs)
    (xs_t, cov, comm, min_counts, min_cov_counts, times,
     stale, cbytes, pbytes) = outs
    xs = jnp.concatenate([jnp.stack([jnp.zeros(d), x1]), xs_t], axis=0)
    if hier:
        xs = xs[:, None, :]   # out_spec stacks pods along this axis
    tau, tau_cov = _tau_pair(min_counts, min_cov_counts, n_pop)
    return xs, cov, comm, tau, tau_cov, times, stale, cbytes, pbytes


def _sharded_out_specs(pod_axis: str, hspec: HierarchySpec | None):
    """Outputs are replicated by construction (every x-update flows
    through the psum); a hierarchical run stacks its per-pod iterates
    along the pod axis."""
    if hspec is not None:
        return (P(None, pod_axis, None),) + (P(),) * 8
    return (P(),) * 9


_SHARDED_STATIC = ("mesh", "data_axis", "model_axis", "pod_axis",
                   "num_rounds", "num_regions", "controller", "mu", "lr",
                   "curvature", "use_kernel", "interpret", "cho_lower",
                   "overlap", "qspec", "comp", "hspec")


def _sharded_engine(problem, k_loop, x1, C0, chol, hdiag, cost, *, mesh,
                    data_axis, model_axis, pod_axis, num_rounds,
                    num_regions, controller, mu, lr, curvature, use_kernel,
                    interpret, cho_lower, overlap, qspec=None, comp=None,
                    hspec=None):
    """The round loop shard-mapped over the placement's mesh, from an init
    state computed outside it (replicated, or the O(d) diag init)."""
    body = functools.partial(
        _sharded_rounds_body, data_axis=data_axis, model_axis=model_axis,
        num_rounds=num_rounds, num_regions=num_regions,
        controller=controller, mu=mu, lr=lr, curvature=curvature,
        use_kernel=use_kernel, interpret=interpret, cho_lower=cho_lower,
        num_workers=problem.num_workers, n_data=mesh.shape[data_axis],
        n_model=1 if model_axis is None else mesh.shape[model_axis],
        overlap=overlap, qspec=qspec, comp=comp, pod_axis=pod_axis,
        hspec=hspec)
    # hier: workers shard JOINTLY over (pod, data) — pod-major layout,
    # matching the body's (me_pod * n_data + me_d) slice arithmetic
    waxis = (pod_axis, data_axis) if hspec is not None else data_axis
    if model_axis is None:
        problem_spec, memory_spec = (_worker_sharded_specs(problem, waxis),
                                     P(waxis, None))
        hdiag_spec = _replicated_specs(hdiag)
    else:
        from ..launch.shard import ranl2d_pspecs
        specs = ranl2d_pspecs(problem, worker_axis=waxis,
                              dim_axis=model_axis)
        problem_spec, memory_spec = specs["problem"], specs["memory"]
        hdiag_spec = specs["hdiag"]
    in_specs = (problem_spec, _replicated_specs(k_loop),
                _replicated_specs(x1), memory_spec, _replicated_specs(chol),
                hdiag_spec, _replicated_specs(cost))
    # check_vma=False: the replication checker cannot track the
    # axis_index-based worker slicing
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=_sharded_out_specs(pod_axis, hspec),
                   check_vma=False)
    return fn(problem, k_loop, x1, C0, chol, hdiag, cost)


_sharded_jit = functools.partial(
    jax.jit, static_argnames=_SHARDED_STATIC)(_sharded_engine)


def _check_mesh(problem, mesh, data_axis: str, model_axis: str | None = None):
    """Validate the placement's mesh: its axes, and that the workers
    (and, with ``model_axis``, the dimension) divide across them."""
    if model_axis is None:
        if data_axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no "
                             f"{data_axis!r} axis to shard workers over")
    else:
        for ax in (data_axis, model_axis):
            if ax not in mesh.axis_names:
                raise ValueError(
                    f"mesh {mesh.axis_names} has no {ax!r} axis — the "
                    f"sharded2d engine needs a ({data_axis!r}, "
                    f"{model_axis!r}) mesh")
    n_data = mesh.shape[data_axis]
    if problem.num_workers % n_data:
        raise ValueError(
            f"num_workers={problem.num_workers} must divide evenly across "
            f"the {n_data} devices of the {data_axis!r} mesh axis")
    n_model = 1 if model_axis is None else mesh.shape[model_axis]
    if problem.dim % n_model:
        raise ValueError(
            f"dim={problem.dim} must divide evenly across the {n_model} "
            f"devices of the {model_axis!r} mesh axis")


def _check_pod_mesh(problem, mesh, axis_name: str, pod_axis: str,
                    hspec: HierarchySpec, num_rounds: int):
    """Hierarchical mesh validation shared by the sharded engines: the
    mesh must carry a ``pod_axis`` whose extent IS the pod count, and
    each pod's sub-population must divide across the data axis."""
    _check_hier(problem, hspec, num_rounds)
    if pod_axis not in mesh.axis_names:
        raise ValueError(
            f"hierarchy pods={hspec.pods} needs a {pod_axis!r} axis on "
            f"the mesh (got {mesh.axis_names}; build one with "
            f"launch.mesh.make_engine_mesh(..., pods=...))")
    if mesh.shape[pod_axis] != hspec.pods:
        raise ValueError(
            f"hierarchy pods={hspec.pods} != mesh {pod_axis!r} axis "
            f"extent {mesh.shape[pod_axis]}")
    n_pop = problem.num_workers // hspec.pods
    n_data = mesh.shape[axis_name]
    if n_pop % n_data:
        raise ValueError(
            f"per-pod workers {n_pop} must divide evenly across the "
            f"{n_data} devices of the {axis_name!r} mesh axis")


def _sharded_projection(opts: RanlOptions, model_axis: str | None) -> str:
    """The engine default for ``projection=None``: Newton–Schulz on the
    2-D dense path (no device may hold the d×d buffer eigh needs),
    ``eigh`` everywhere else."""
    if opts.projection is not None:
        return opts.projection
    dense2d = model_axis is not None and opts.curvature == "dense"
    return "ns" if dense2d else "eigh"


def _sharded_args(problem, key, opts: RanlOptions, *, mesh, data_axis,
                  model_axis, controller, cost, pod_axis: str = "pod",
                  abstract: bool = False):
    """-> (jitted_engine, args, static) for the placement and curvature.

    2-D dense: the ENTIRE program — init included — is one shard_map'd
    computation over (problem, key, cost), so lowering it exposes every
    phase to the HLO memory/communication assertions and nothing
    replicated ever materializes host-side.  Otherwise the init runs
    host-side exactly as in the scan engine (replicated on the
    ``("data",)`` placement, including its ``projection`` and
    ``hessian_rank`` knobs; the O(d) Hutchinson init on the 2-D one) and
    only the round loop is shard_map'd; with ``abstract=True`` the init
    is traced to avals via ``jax.eval_shape`` so lowering pays no
    compute.
    """
    _check_mesh(problem, mesh, data_axis, model_axis)
    hspec = opts.hierarchy_spec()
    if hspec is not None:
        _check_pod_mesh(problem, mesh, data_axis, pod_axis, hspec,
                        int(opts.num_rounds))
    controller, cost = _hetero_defaults(problem, opts.policy, controller,
                                        cost)
    if (model_axis is not None and opts.curvature == "dense"
            and opts.projection == "eigh"):
        raise ValueError(
            "projection='eigh' is not implementable on the 2-D dense path "
            "(no device may hold a d×d buffer) — use projection='ns' or "
            "leave projection=None for the engine default")
    projection = _sharded_projection(opts, model_axis)
    cfg = _config(problem, mu=opts.mu, lr=opts.lr,
                  curvature=opts.curvature,
                  hutchinson_samples=opts.hutchinson_samples,
                  projection=projection)
    hutch = cfg.pop("hutch_samples")
    static = dict(mesh=mesh, data_axis=data_axis, model_axis=model_axis,
                  pod_axis=pod_axis, num_rounds=int(opts.num_rounds),
                  num_regions=int(opts.num_regions),
                  controller=controller, mu=cfg["mu"], lr=cfg["lr"],
                  overlap=bool(opts.overlap), qspec=opts.quorum_spec(),
                  comp=opts.compression_spec(), hspec=hspec)
    if model_axis is not None and cfg["curvature"] == "dense":
        static["ns_iters"] = (opts.ns_iters if opts.ns_iters == "auto"
                              else int(opts.ns_iters))
        return _sharded2d_dense_jit, (problem, key, cost), static

    init_static = {}

    def make_args(problem, key):
        k_init, k_loop = jax.random.split(key)
        x1, C0, chol, init_static["cho_lower"], hdiag = _init_phase(
            problem, k_init, mu=cfg["mu"], lr=cfg["lr"],
            curvature=cfg["curvature"], hutch_samples=hutch,
            projection=projection, ns_iters=opts.ns_iters,
            hessian_rank=opts.hessian_rank)
        return problem, k_loop, x1, C0, chol, hdiag

    if abstract:
        args = jax.eval_shape(make_args, problem, key)
    else:
        args = make_args(problem, key)
    static.update(curvature=cfg["curvature"],
                  use_kernel=bool(opts.use_kernel), interpret=None,
                  **init_static)
    return _sharded_jit, (*args, cost), static


def _run_sharded(problem, key, opts: RanlOptions, *, mesh,
                 data_axis: str = "data", model_axis: str | None = None,
                 pod_axis: str = "pod", controller=None, cost=None):
    """Algorithm 1 with the worker axis sharded across the ``data_axis``
    devices of ``mesh`` — and, with ``model_axis``, the parameter
    dimension across that axis (engines ``"sharded"`` and ``"sharded2d"``
    of ``repro.run``).

    The round loop runs under ``shard_map`` with ``problem``'s
    worker-indexed leaves and the gradient memory C partitioned, and
    server aggregation expressed as ``psum`` collectives (see
    ``_sharded_rounds_body``).  On the ``("data",)`` placement the init
    phase runs replicated (identical to the scan engine, including its
    ``projection`` knob) and the aggregation is always the pure-jnp
    collective form; trajectories match the scan engine to
    reduction-reorder tolerance (parity-pinned at 1e-6 in
    tests/test_multidevice.py).

    With ``model_axis`` each device holds d/n_model-row slices of C, the
    pruned gradients, ``hdiag`` and the region masks, and the per-round
    param all-reduce shrinks to a psum of d/n_model floats over ONLY the
    data axis.  ``curvature="dense"`` runs the WHOLE dense path sharded,
    init included: the mean Hessian accumulates as model-axis row panels
    (``worker_hessian_rows``), the Definition-4 projection is the
    matmul-only Newton–Schulz iteration over those panels (``ns_iters``
    controls its step count — see ``hessian.project_psd_ns``), and the
    blocked right-looking factorization + blocked triangular solves
    replace the replicated Cholesky.  No device materializes a d×d
    buffer at ANY phase, proven on compiled HLO.  The single-device
    oracle of this path is the scan engine with ``projection="ns"``.
    ``curvature="diag"`` keeps the O(d)-state Hutchinson init; its
    estimate and the fused Pallas ``ranl_update`` kernel run on local
    d-slices unchanged (the kernel engages on pure model-parallel
    meshes, where every worker is device-local).  Parity with the
    matching single-device oracle is pinned at 1e-5 on 1x1, 2x2 and 1x4
    emulated meshes.

    ``opts.overlap`` selects the double-buffered round loop (next round's
    mask sampling and coverage-count psum pipelined into the param-psum
    window — identical math).  Requires ``num_workers`` divisible by the
    data axis extent and ``dim`` divisible by the model axis extent.
    """
    if opts.num_rounds <= 0:  # no rounds -> no communication to shard
        _check_mesh(problem, mesh, data_axis, model_axis)
        fallback = opts.merged(projection=_sharded_projection(opts,
                                                              model_axis))
        return _run_scan(problem, key, fallback, controller=controller,
                         cost=cost)
    engine, args, static = _sharded_args(
        problem, key, opts, mesh=mesh, data_axis=data_axis,
        model_axis=model_axis, controller=controller, cost=cost,
        pod_axis=pod_axis)
    with span("ranl.rounds"):
        out = engine(*args, **static)
    return _sharded_result(problem, opts, static["hspec"], out)


def _sharded_result(problem, opts: RanlOptions, hspec, out):
    """A sharded engine's outputs as a RanlResult: the pod mean of a
    hierarchical run, the distance and loss traces, and the host reads
    of the coverage pair, where the host waits for the round loop."""
    with span("ranl.result"):
        (xs, cov, comm, tau, tau_cov, times, stale, cbytes, pbytes) = out
        xs_pods = None
        if hspec is not None:
            xs_pods, xs = xs, xs.mean(axis=1)
        dist = jnp.sum((xs - problem.x_star[None, :]) ** 2, axis=1)
        losses = jax.vmap(problem.loss)(xs)
        return _subsampled(RanlResult(
            xs=xs, dist_sq=dist, losses=losses, coverage=cov,
            comm_floats=comm, tau_star=int(tau), tau_covered=int(tau_cov),
            round_time=times, max_stale=stale, comm_bytes=cbytes,
            pod_bytes=pbytes, xs_pods=xs_pods),
            opts.record_every)


def _lower_sharded(problem, key, opts: RanlOptions, *, mesh,
                   data_axis: str = "data", model_axis: str | None = None,
                   pod_axis: str = "pod", controller=None, cost=None):
    """Lower (without running) a sharded engine's program.

    Returns the ``jax.stages.Lowered`` for the same computation
    ``_run_sharded`` executes; the init runs as avals, so nothing
    executes and configs far beyond this host's memory can be inspected.
    ``.compile().as_text()`` is the partitioned HLO that
    ``launch.hlo_analysis`` inventories: exactly one param-sized
    all-reduce over the data axis per round (``overlap=True`` included:
    pipelining moves collectives across iteration boundaries but never
    adds one; controller-driven and quorum runs included: the controller
    steps replicated and the late fold rides the existing psum), and,
    for the 2-D dense program, whose HLO carries every phase, no
    per-device buffer above ~d²/n_model bytes anywhere, init included.
    """
    engine, args, static = _sharded_args(
        problem, key, opts, mesh=mesh, data_axis=data_axis,
        model_axis=model_axis, controller=controller, cost=cost,
        pod_axis=pod_axis, abstract=True)
    return engine.lower(*args, **static)


# --------------------------------------------------------------------------
# the 2-D dense program: on a ("data", "model") mesh with
# curvature="dense" each device holds a (d/n_model, d) row panel of the
# Cholesky factor of [H]_μ, built in-program, so no device ever holds a
# d×d curvature buffer
# --------------------------------------------------------------------------

def _factor_sharded2d_body(h_panel, *, model_axis: str, n_model: int):
    """Blocked right-looking Cholesky over row panels (under shard_map).

    Each device holds the (p, d) row panel of [H]_μ for its model shard
    and finishes holding the same rows of the lower factor L — the
    ``blocked_cholesky`` schedule with the column-block loop mapped onto
    devices.  Iteration j: device j factors its diagonal block (broadcast
    as a (p, p) psum), every device below panel-solves its piece of
    column block j, the finished column block is gathered once, and the
    trailing update is applied locally.  Per-device peak state is the
    (p, d) panel plus one transient (d, p) column block (the "block
    slack" in the memory budget).
    """
    me = jax.lax.axis_index(model_axis)
    p = h_panel.shape[0]
    W = h_panel
    for j in range(n_model):
        s = j * p
        blk = jax.lax.dynamic_slice(W, (0, s), (p, p))
        diag_j = jax.lax.psum(jnp.where(me == j, blk, 0.0), model_axis)
        l_jj = jnp.linalg.cholesky(diag_j)
        below = jax.scipy.linalg.solve_triangular(l_jj, blk.T, lower=True).T
        # rows above block j are strictly upper triangle -> 0 in L
        col = jnp.where(me == j, l_jj, jnp.where(me > j, below, 0.0))
        W = jax.lax.dynamic_update_slice(W, col, (0, s))
        if j + 1 < n_model:
            col_all = jax.lax.all_gather(col, model_axis).reshape(-1, p)
            e = (j + 1) * p
            W = W.at[:, e:].add(-(col @ col_all[e:, :].T))
    return W


def _blocked_solve_panels(l_panel, g_local, *, model_axis: str,
                          n_model: int, me, row_start, dim: int):
    """Solve (L Lᵀ) s = g across row panels; returns the FULL (d,) step.

    ``l_panel``: this device's (p, d) rows of L; ``g_local``: its (p,)
    gradient shard (already data-axis reduced).  Block forward/backward
    substitution with the block loop over model shards: every collective
    is a model-axis psum of at most d floats (the freshly solved block, or
    the running Lᵀs product) — the d axis never gathers, and the backward
    sweep's broadcasts assemble the full step for free, which the caller
    needs anyway to advance the replicated iterate.
    """
    p = l_panel.shape[0]
    diag = jax.lax.dynamic_slice(l_panel, (0, row_start), (p, p))
    zeros = jnp.zeros((dim,), l_panel.dtype)

    y = zeros                                    # forward: L y = g
    for j in range(n_model):
        # on device j: g_j - sum_{k<j} L_jk y_k (unsolved blocks of y are 0)
        rhs = g_local - l_panel @ y
        cand = jax.scipy.linalg.solve_triangular(diag, rhs, lower=True)
        mine = jnp.where(me == j, cand, 0.0)
        y = y + jax.lax.psum(
            jax.lax.dynamic_update_slice(zeros, mine, (row_start,)),
            model_axis)

    y_local = jax.lax.dynamic_slice(y, (row_start,), (p,))
    s = zeros                                    # backward: Lᵀ s = y
    for j in reversed(range(n_model)):
        s_local = jax.lax.dynamic_slice(s, (row_start,), (p,))
        lts = jax.lax.psum(l_panel.T @ s_local, model_axis)   # full Lᵀ s
        rhs = y_local - jax.lax.dynamic_slice(lts, (row_start,), (p,))
        cand = jax.scipy.linalg.solve_triangular(diag.T, rhs, lower=False)
        mine = jnp.where(me == j, cand, 0.0)
        s = s + jax.lax.psum(
            jax.lax.dynamic_update_slice(zeros, mine, (row_start,)),
            model_axis)
    return s


def _sharded2d_dense_body(problem, key, cost, *, data_axis, model_axis,
                          num_rounds, num_regions, controller, mu, lr,
                          ns_iters, overlap, num_workers, n_data, n_model,
                          qspec=None, comp=None, pod_axis="pod",
                          hspec=None):
    """Dense-curvature 2-D program, init INCLUDED (runs under shard_map).

    Alg. 1 lines 1–8 with every d-sized object as model-axis row panels:

    * the mean worker Hessian accumulates as a running sum of
      ``worker_hessian_rows`` panels (``lax.scan`` over local workers,
      one data-axis psum) — peak O(d²/n_model), never O(N·d²);
    * the Definition-4 projection is the matmul-only Newton–Schulz
      iteration over those panels (``project_psd_ns_panels``) — no eigh,
      no replicated d×d buffer, the panel-product psums stay on the
      model axis;
    * the blocked right-looking factorization and the blocked-solve first
      Newton step complete the phase, and the round loop continues with
      the factor's row panels in place.

    The largest per-device buffer across the WHOLE program is the
    (d/n_model, d) panel — asserted on the compiled HLO by
    tests via ``hlo_analysis.max_array_bytes``.
    """
    N = num_workers
    d = problem.dim
    p = d // n_model
    n_local = problem.num_workers         # workers held by this shard
    me_d = jax.lax.axis_index(data_axis)
    me_m = jax.lax.axis_index(model_axis)
    hier = hspec is not None
    me_pod = jax.lax.axis_index(pod_axis) if hier else 0
    wstart = ((me_pod * n_data + me_d) * n_local if hier
              else me_d * n_local)
    # the init phase is GLOBAL in every mode (Alg. 1's mean Hessian and
    # mean gradient use all N workers) — under hier its two psums reduce
    # jointly over the data AND pod axes, once, outside the round loop
    worker_axes = (data_axis, pod_axis) if hier else data_axis
    row_start = me_m * p
    local_ids = jnp.arange(n_local)
    k_init, k_loop = jax.random.split(key)
    x0 = jnp.zeros(d)
    hkeys = jax.lax.dynamic_slice_in_dim(
        jax.random.split(jax.random.fold_in(k_init, 0), N), wstart, n_local)
    gkeys = jax.lax.dynamic_slice_in_dim(
        jax.random.split(jax.random.fold_in(k_init, 1), N), wstart, n_local)

    def acc(h_sum, ik):
        i, k = ik
        return h_sum + problem.worker_hessian_rows(i, x0, k, row_start,
                                                   p), None

    h_panel, _ = jax.lax.scan(acc, jnp.zeros((p, d)), (local_ids, hkeys))
    h_panel = jax.lax.psum(h_panel, worker_axes) / N
    hmu_panel = project_psd_ns_panels(h_panel, mu, axis_name=model_axis,
                                      n_model=n_model, num_iters=ns_iters)
    chol = _factor_sharded2d_body(hmu_panel, model_axis=model_axis,
                                  n_model=n_model)
    g0 = jax.vmap(lambda i, k: problem.worker_grad_rows(
        i, x0, k, row_start, p))(local_ids, gkeys)       # (n_local, p)
    gbar_loc = jax.lax.psum(g0.sum(axis=0), worker_axes) / N
    step0 = _blocked_solve_panels(chol, gbar_loc, model_axis=model_axis,
                                  n_model=n_model, me=me_m,
                                  row_start=row_start, dim=d)
    x1 = x0 - lr * step0
    return _sharded_rounds_body(
        problem, k_loop, x1, g0, chol, None, cost, data_axis=data_axis,
        model_axis=model_axis, num_rounds=num_rounds,
        num_regions=num_regions, controller=controller, mu=mu, lr=lr,
        curvature="dense", use_kernel=False, interpret=None,
        cho_lower=True, num_workers=N, n_data=n_data, n_model=n_model,
        overlap=overlap, qspec=qspec, comp=comp, pod_axis=pod_axis,
        hspec=hspec)


_SHARDED2D_DENSE_STATIC = ("mesh", "data_axis", "model_axis", "pod_axis",
                           "num_rounds", "num_regions", "controller", "mu",
                           "lr", "ns_iters", "overlap", "qspec", "comp",
                           "hspec")


def _sharded2d_dense_engine(problem, key, cost, *, mesh, data_axis,
                            model_axis, pod_axis, num_rounds, num_regions,
                            controller, mu, lr, ns_iters, overlap,
                            qspec=None, comp=None, hspec=None):
    from ..launch.shard import ranl2d_pspecs
    body = functools.partial(
        _sharded2d_dense_body, data_axis=data_axis, model_axis=model_axis,
        num_rounds=num_rounds, num_regions=num_regions,
        controller=controller, mu=mu, lr=lr, ns_iters=ns_iters,
        overlap=overlap, num_workers=problem.num_workers,
        n_data=mesh.shape[data_axis], n_model=mesh.shape[model_axis],
        qspec=qspec, comp=comp, pod_axis=pod_axis, hspec=hspec)
    waxis = (pod_axis, data_axis) if hspec is not None else data_axis
    specs = ranl2d_pspecs(problem, worker_axis=waxis,
                          dim_axis=model_axis)
    in_specs = (specs["problem"], _replicated_specs(key),
                _replicated_specs(cost))
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=_sharded_out_specs(pod_axis, hspec),
                   check_vma=False)
    return fn(problem, key, cost)


_sharded2d_dense_jit = functools.partial(
    jax.jit, static_argnames=_SHARDED2D_DENSE_STATIC)(
    _sharded2d_dense_engine)


def _config(problem, *, mu, lr, curvature, hutchinson_samples,
            projection: str = "eigh"):
    if curvature not in ("dense", "diag"):
        raise ValueError(f"unknown curvature {curvature!r}")
    if projection not in ("eigh", "ns"):
        raise ValueError(f"unknown projection {projection!r}")
    return dict(mu=float(problem.mu) if mu is None else float(mu),
                lr=float(lr), curvature=curvature,
                hutch_samples=int(hutchinson_samples))


def _subsampled(result: RanlResult, record_every: int) -> RanlResult:
    """Post-hoc iterate thinning for ``record_every > 1``.

    Keeps x⁰, x¹ (post-init), every ``record_every``-th round's iterate
    and the final one, on the iterate-indexed arrays (``xs``/``dist_sq``/
    ``losses`` — batched runs thin along their iterate axis).  Per-round
    traces (coverage/comm/round_time/max_stale) stay full length: they
    are what the time-to-target and telemetry analyses consume.
    """
    k = int(record_every)
    if k <= 1:
        return result
    T = result.dist_sq.shape[-1] - 2
    rounds = sorted(set(range(k, T + 1, k)) | ({T} if T > 0 else set()))
    idx = jnp.asarray([0, 1] + [1 + r for r in rounds], jnp.int32)
    xs_pods = result.xs_pods
    if xs_pods is not None:
        xs_pods = jnp.take(xs_pods, idx, axis=xs_pods.ndim - 3)
    return dc_replace(
        result,
        xs=jnp.take(result.xs, idx, axis=result.xs.ndim - 2),
        xs_pods=xs_pods,
        dist_sq=jnp.take(result.dist_sq, idx, axis=-1),
        losses=jnp.take(result.losses, idx, axis=-1))


def _scan_args(problem, key, opts: RanlOptions, *, controller=None,
               cost=None):
    """-> (args, static) for ``_scan_rounds`` — the init phase runs (or
    traces) here; shared by ``_run_scan`` and the jaxpr-audit hook
    ``trace_ranl`` so the audited program is the executed program."""
    ctrl, cost = _hetero_defaults(problem, opts.policy, controller, cost)
    hspec = opts.hierarchy_spec()
    _check_hier(problem, hspec, int(opts.num_rounds))
    projection = opts.projection or "eigh"
    cfg = _config(problem, mu=opts.mu, lr=opts.lr,
                  curvature=opts.curvature,
                  hutchinson_samples=opts.hutchinson_samples,
                  projection=projection)
    hutch = cfg.pop("hutch_samples")
    k_init, k_loop = jax.random.split(key)
    x1, C0, cho_c, cho_lower, hdiag = _init_phase(
        problem, k_init, mu=cfg["mu"], lr=cfg["lr"],
        curvature=cfg["curvature"], hutch_samples=hutch,
        projection=projection, ns_iters=opts.ns_iters,
        hessian_rank=opts.hessian_rank)
    args = (problem, k_loop, x1, C0, cho_c, hdiag, cost)
    static = dict(num_rounds=int(opts.num_rounds),
                  num_regions=int(opts.num_regions),
                  controller=ctrl, use_kernel=bool(opts.use_kernel),
                  interpret=None, cho_lower=cho_lower,
                  qspec=opts.quorum_spec(),
                  comp=opts.compression_spec(), hspec=hspec, **cfg)
    return args, static


def _run_scan(problem, key, opts: RanlOptions, *, controller=None,
              cost=None):
    """Algorithm 1 as one compiled ``lax.scan`` (engine ``"scan"`` of
    ``repro.run``).  Returns RanlResult.

    ``opts.curvature="dense"`` (default) keeps the exact Definition-4
    projection — ``projection=None``/``"eigh"`` via eigenvalue clamping,
    ``"ns"`` via the matmul-only Newton–Schulz form (``ns_iters`` steps
    or ``"auto"``; the single-device oracle of the dimension-sharded
    init).  ``"diag"`` uses a Hutchinson diagonal estimate and the fused
    Pallas update kernel (``use_kernel=False`` for the pure-jnp oracle).

    ``controller`` (a ``repro.hetero`` Controller; overrides
    ``opts.policy``) closes the heterogeneity loop; ``cost`` (a
    ``CostModel``) prices every round.  ``opts.quorum`` switches the
    rounds semi-synchronous (see ``_scan_rounds``).
    """
    args, static = _scan_args(problem, key, opts, controller=controller,
                              cost=cost)
    with span("ranl.rounds"):
        (xs, dist, losses, cov, comm, tau, tau_cov, times, stale,
         cbytes, pbytes) = _rounds_jit(*args, **static)
    with span("ranl.result"):
        xs_pods = None
        if static["hspec"] is not None:
            xs_pods, xs = xs, xs.mean(axis=1)
        return _subsampled(RanlResult(
            xs=xs, dist_sq=dist, losses=losses, coverage=cov,
            comm_floats=comm, tau_star=int(tau), tau_covered=int(tau_cov),
            round_time=times, max_stale=stale, comm_bytes=cbytes,
            pod_bytes=pbytes, xs_pods=xs_pods,
            grad_path=problem.grad_path(static["use_kernel"],
                                        static["interpret"])),
            opts.record_every)


def _batch_use_kernel(opts: RanlOptions, mesh, axis_name: str) -> bool:
    """The batch engine's ``use_kernel``: XLA cannot partition a Pallas
    call, so seeds spread over several devices take the jnp forms, which
    each device runs for its own seeds."""
    return bool(opts.use_kernel) and (mesh is None
                                      or mesh.shape[axis_name] == 1)


def _run_batch(problem, keys, opts: RanlOptions, *, mesh=None,
               axis_name: str = "data", controller=None, cost=None):
    """Batched multi-seed runs (engine ``"batch"`` of ``repro.run``):
    one compilation, vmapped over ``keys``.

    ``keys``: (B,)-stacked PRNG keys (``jax.random.split(key, B)``).
    Returns a RanlResult whose arrays carry a leading batch axis and whose
    ``tau_star`` is a (B,) int array.

    With ``mesh``, the seed axis is sharded across the devices of the
    mesh's ``axis_name`` axis (the problem is replicated): B independent
    runs execute B/n_dev-per-device with zero cross-run communication.
    Requires B divisible by the axis extent.  Over more than one device
    the runs take the jnp forms, not the Pallas kernels
    (``_batch_use_kernel``).

    ``controller``/``cost`` close the heterogeneity loop per seed (each
    vmapped run carries its own controller state and telemetry);
    ``round_time``/``max_stale`` come back (B, T)-shaped.
    """
    ctrl, cost = _hetero_defaults(problem, opts.policy, controller, cost)
    hspec = opts.hierarchy_spec()
    _check_hier(problem, hspec, int(opts.num_rounds))
    keys = jnp.asarray(keys)
    if mesh is not None:
        if axis_name not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no "
                             f"{axis_name!r} axis to shard seeds over")
        n_dev = mesh.shape[axis_name]
        if keys.shape[0] % n_dev:
            raise ValueError(
                f"batch of {keys.shape[0]} seeds must divide evenly "
                f"across the {n_dev} devices of the {axis_name!r} axis")
        # the seed axis is sharded by placement alone, which needs Auto
        # axes: on an Explicit-axis mesh (``jax.make_mesh``'s default) the
        # sharding would enter the traced types and the vmapped rounds
        # could not resolve it
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        keys = jax.device_put(keys, NamedSharding(mesh, P(axis_name)))
        problem = jax.device_put(problem, NamedSharding(mesh, P()))
        cost = jax.device_put(cost, NamedSharding(mesh, P()))
    use_kernel = _batch_use_kernel(opts, mesh, axis_name)
    projection = opts.projection or "eigh"
    cfg = _config(problem, mu=opts.mu, lr=opts.lr,
                  curvature=opts.curvature,
                  hutchinson_samples=opts.hutchinson_samples,
                  projection=projection)
    (xs, dist, losses, cov, comm, tau, tau_cov, times, stale,
     cbytes, pbytes) = _batch_jit(
        problem, keys, cost, num_rounds=int(opts.num_rounds),
        num_regions=int(opts.num_regions), controller=ctrl,
        use_kernel=use_kernel, interpret=None,
        projection=projection,
        ns_iters=opts.ns_iters if opts.ns_iters == "auto"
        else int(opts.ns_iters),
        qspec=opts.quorum_spec(), comp=opts.compression_spec(),
        hessian_rank=opts.hessian_rank, hspec=hspec, **cfg)
    xs_pods = None
    if hspec is not None:
        xs_pods, xs = xs, xs.mean(axis=2)
    return _subsampled(RanlResult(
        xs=xs, dist_sq=dist, losses=losses, coverage=cov,
        comm_floats=comm, tau_star=tau, tau_covered=tau_cov,
        round_time=times, max_stale=stale, comm_bytes=cbytes,
        pod_bytes=pbytes, xs_pods=xs_pods,
        grad_path=problem.grad_path(use_kernel, None)),
        opts.record_every)


def _reference_program(problem, key, cost, *, opts: RanlOptions,
                       controller):
    """The reference engine's round loop as a pure array program.

    Factored out of ``_run_reference`` so it is traceable end to end
    (``jax.make_jaxpr`` / ``jax.jit``) for the static auditors: the
    over-rounds coverage minima accumulate with ``jnp.minimum`` instead
    of host-side ``int()``/``min()`` — identical values, the final
    ``int()`` conversions stay in the caller.  Returns the raw arrays
    ``(xs, cov, comm, tau, tau_cov, times, stale, cbytes)``.
    """
    from ..hetero.controller import initial_telemetry, next_telemetry
    from ..hetero.cost import quorum_split, worker_times
    num_rounds, num_regions = opts.num_rounds, opts.num_regions
    ctrl = controller
    qspec = opts.quorum_spec()
    comp = opts.compression_spec()
    mu = problem.mu if opts.mu is None else opts.mu
    lr = float(opts.lr)
    N, d = problem.num_workers, problem.dim
    Q = num_regions
    region_ids = contiguous_regions(d, Q)
    sizes_q = region_sizes(region_ids, Q)
    k_init, k_loop = jax.random.split(key)

    x0 = jnp.zeros(d)
    hkeys = jax.random.split(jax.random.fold_in(k_init, 0), N)
    gkeys = jax.random.split(jax.random.fold_in(k_init, 1), N)
    H_mu = project_psd(running_mean_hessian(problem, x0, hkeys), mu)
    g0 = jnp.stack([problem.worker_grad(i, x0, gkeys[i]) for i in range(N)])
    C = g0
    x = x0 - lr * solve_projected(H_mu, g0.mean(axis=0))

    worker_ids = jnp.arange(N)
    grad_all = jax.vmap(problem.worker_grad, in_axes=(0, 0, 0))

    xs = [x0, x]
    min_cov = jnp.asarray(N, jnp.int32)
    min_cov_covered = jnp.asarray(N, jnp.int32)
    cov_hist, comm_hist, time_hist, stale_hist = [], [], [], []
    bytes_hist = []
    ctrl_state = ctrl.init_state(N, Q)
    telem = initial_telemetry(N, Q)
    late_buf = (None if qspec is None
                else jnp.zeros((qspec.max_delay, d)))
    err = (None if comp is None else jnp.zeros((N, d)))
    for t in range(1, num_rounds + 1):
        kt = jax.random.fold_in(k_loop, t)
        M, ctrl_state = _controller_mask(ctrl, cost, ctrl_state, telem,
                                         kt, t, N, Q)   # (N, Q) bool
        Mx = expand_mask(M, region_ids)                  # (N, d) bool
        x_pruned = jnp.where(Mx, x[None, :], 0.0)        # x ⊙ m_i
        gk = jax.random.split(jax.random.fold_in(kt, 7), N)
        G = grad_all(worker_ids, x_pruned, gk) * Mx      # ∇F_i ⊙ m_i
        ubytes = uplink_bytes(comp, M, sizes_q)
        if qspec is None:
            if comp is None:
                g, C = server_aggregate(G, Mx, C)
            else:
                g, C, err = compressed_server_aggregate(
                    G, Mx, C, err, comp, region_ids=region_ids,
                    num_regions=Q)
            count_q = M.sum(axis=0)
            telem = _observe_round(cost, telem, M, count_q, sizes_q, t,
                                   ubytes)
            round_t = telem.times.max()
        else:
            work = (M * sizes_q[None, :]).sum(axis=1)
            times = worker_times(cost, work, t, ubytes)
            deadline, on_time, delays = quorum_split(
                times, M, quorum=qspec.quorum,
                quorum_tau=qspec.quorum_tau, max_delay=qspec.max_delay)
            if comp is None:
                g, C, late_buf = quorum_aggregate(
                    G, Mx, C, on_time, delays, late_buf,
                    gamma=qspec.gamma, max_delay=qspec.max_delay)
            else:
                g, C, err, late_buf = compressed_quorum_aggregate(
                    G, Mx, C, err, on_time, delays, late_buf, comp,
                    region_ids=region_ids, num_regions=Q,
                    gamma=qspec.gamma, max_delay=qspec.max_delay)
            count_q = (M & on_time[:, None]).sum(axis=0)  # on-time counts
            telem = next_telemetry(telem, count_q, work, times)
            round_t = deadline
        x = x - lr * solve_projected(H_mu, g)
        xs.append(x)

        cov_mean, min_count, min_cov_count = _round_diagnostics(
            count_q > 0, count_q, N)
        cov_hist.append(cov_mean)
        comm_hist.append(Mx.sum())                       # uplink floats
        bytes_hist.append(ubytes.sum())                  # uplink bytes
        time_hist.append(round_t)
        stale_hist.append(telem.stale_q.max())
        min_cov = jnp.minimum(min_cov, min_count)
        min_cov_covered = jnp.minimum(min_cov_covered, min_cov_count)

    xs = jnp.stack(xs)
    return (xs, jnp.stack(cov_hist), jnp.stack(comm_hist), min_cov,
            min_cov_covered, jnp.stack(time_hist), jnp.stack(stale_hist),
            jnp.stack(bytes_hist))


def _run_reference(problem, key, opts: RanlOptions, *, controller=None,
                   cost=None):
    """Original host-loop driver (engine ``"reference"`` of ``repro.run``;
    re-traces every round).

    Kept as the semantic oracle: the scan engine must reproduce its
    trajectory on a fixed key, and the engine-speedup benchmark measures
    against it.  ``controller``/``cost`` run the same closed loop
    eagerly, and ``opts.quorum`` runs the same eager rounds through
    ``quorum_split``/``quorum_aggregate`` — the host-loop oracle of the
    engines' semi-synchronous path.  Dense ``eigh`` curvature only (the
    dispatcher enforces this).  The loop itself lives in
    ``_reference_program`` (traceable for the static auditors).
    """
    ctrl, cost = _hetero_defaults(problem, opts.policy, controller, cost)
    xs, cov, comm, min_cov, min_cov_covered, times, stale, cbytes = \
        _reference_program(problem, key, cost, opts=opts, controller=ctrl)
    dist = jnp.sum((xs - problem.x_star[None, :]) ** 2, axis=1)
    losses = jnp.stack([problem.loss(xi) for xi in xs])
    return _subsampled(RanlResult(
        xs=xs, dist_sq=dist, losses=losses,
        coverage=cov, comm_floats=comm,
        tau_star=int(min_cov), tau_covered=int(min_cov_covered),
        round_time=times, max_stale=stale,
        comm_bytes=cbytes), opts.record_every)


def trace_ranl(problem, key, opts: RanlOptions = RanlOptions(), *,
               engine: str = "scan", mesh=None, axis_name: str = "data",
               data_axis: str = "data", model_axis: str = "model",
               pod_axis: str = "pod", controller=None, cost=None):
    """Closed jaxpr of the FULL engine program (init phase + round loop).

    The pre-compile artifact ``repro.analysis.jaxpr_audit`` inventories:
    collective primitives with exact ``lax.scan`` trip counts, PRNG
    consumption, dtype promotion, host-sync hazards.  Every engine
    traces the same computation it executes — the prep helpers
    (``_scan_args`` / ``_sharded_args`` / ``_reference_program``) are shared with the run paths, only wrapped
    in ``jax.make_jaxpr`` here instead of being executed.  For
    ``engine="batch"``, ``key`` is the stacked ``(B,)`` key array the
    batch engine takes.
    """
    ctrl, cost = _hetero_defaults(problem, opts.policy, controller, cost)

    if engine == "scan":
        def program(problem, key, cost):
            args, static = _scan_args(problem, key, opts, controller=ctrl,
                                      cost=cost)
            return _scan_rounds(*args, **static)
    elif engine == "batch":
        projection = opts.projection or "eigh"
        cfg = _config(problem, mu=opts.mu, lr=opts.lr,
                      curvature=opts.curvature,
                      hutchinson_samples=opts.hutchinson_samples,
                      projection=projection)

        def program(problem, keys, cost):
            return _ranl_batch_engine(
                problem, jnp.asarray(keys), cost,
                num_rounds=int(opts.num_rounds),
                num_regions=int(opts.num_regions), controller=ctrl,
                use_kernel=_batch_use_kernel(opts, mesh, axis_name),
                interpret=None, projection=projection,
                ns_iters=opts.ns_iters if opts.ns_iters == "auto"
                else int(opts.ns_iters),
                qspec=opts.quorum_spec(), comp=opts.compression_spec(),
                hessian_rank=opts.hessian_rank,
                hspec=opts.hierarchy_spec(), **cfg)
    elif engine == "reference":
        def program(problem, key, cost):
            return _reference_program(problem, key, cost, opts=opts,
                                      controller=ctrl)
    elif engine in ("sharded", "sharded2d"):
        if mesh is None:
            raise ValueError(f"engine={engine!r} needs a mesh to trace")
        placement = (dict(data_axis=axis_name, model_axis=None)
                     if engine == "sharded"
                     else dict(data_axis=data_axis, model_axis=model_axis))

        def program(problem, key, cost):
            eng, args, static = _sharded_args(
                problem, key, opts, mesh=mesh, controller=ctrl, cost=cost,
                pod_axis=pod_axis, **placement)
            return eng(*args, **static)
    else:
        raise ValueError(f"unknown engine {engine!r}")

    return jax.make_jaxpr(program)(problem, key, cost)
