"""Hierarchical pod-of-pods aggregation tests (the 3-D mesh tentpole).

Covers: ``hierarchy=`` spec parsing and its validation errors, the
``pods=1`` degenerate-parity rail (hierarchical bookkeeping, flat
trajectory), dispatch-time divisibility checks and the reference
oracle's rejection, the ``RanlResult.pod_bytes`` period accounting on a
WAN topology (flat pays the inter-pod links every round, hierarchical
only on exchange rounds — reduced exactly by the period), the pinned
<= 0.8x simulated time-to-target win on the uplink-asymmetric
``geo-distributed`` scenario (the acceptance bound ``benchmarks.claims
.bench_hierarchy`` tracks), and — in the slow subprocess leg — sharded /
sharded2d parity against the scan oracle on an emulated pod mesh plus
the compiled-HLO contract proof that the inter-pod psum carries
multiplier ``E = rounds/period`` while exactly one intra-pod data psum
per round survives.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import PolicyConfig, make_quadratic
from repro.core.options import HierarchySpec, parse_hierarchy
from repro.hetero import make_scenario, time_to_target

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# spec parsing
# --------------------------------------------------------------------------

def test_parse_hierarchy_spec():
    assert parse_hierarchy(None) is None
    assert parse_hierarchy("") is None
    h = parse_hierarchy("pods=2,period=4")
    assert (h.pods, h.period, h.gamma, h.compression) == (2, 4, 1.0, None)
    h = parse_hierarchy("pods=4,period=2,gamma=0.5,compression=int8")
    assert (h.pods, h.period, h.gamma, h.compression) == (4, 2, 0.5, "int8")
    # whitespace-tolerant, and an existing spec passes through unchanged
    assert parse_hierarchy(" pods = 8 ").pods == 8
    spec = HierarchySpec(pods=2, period=3)
    assert parse_hierarchy(spec) is spec
    # RanlOptions surfaces the same parse (validated at construction)
    opts = repro.RanlOptions(hierarchy="pods=2,period=2")
    assert opts.hierarchy_spec() == HierarchySpec(pods=2, period=2)
    assert repro.RanlOptions().hierarchy_spec() is None


def test_parse_hierarchy_errors():
    with pytest.raises(ValueError, match="must set pods"):
        parse_hierarchy("period=2")
    with pytest.raises(ValueError, match="pods=0 must be >= 1"):
        parse_hierarchy("pods=0")
    with pytest.raises(ValueError, match="period=0 must be >= 1"):
        parse_hierarchy("pods=2,period=0")
    with pytest.raises(ValueError, match="gamma"):
        parse_hierarchy("pods=2,gamma=0.0")
    with pytest.raises(ValueError, match="gamma"):
        parse_hierarchy("pods=2,gamma=1.5")
    with pytest.raises(ValueError, match="intra-pod only"):
        parse_hierarchy("pods=2,compression=topk2")
    with pytest.raises(ValueError, match="unknown hierarchy key"):
        parse_hierarchy("pods=2,periods=4")
    with pytest.raises(ValueError, match="expected key=value"):
        parse_hierarchy("pods")


# --------------------------------------------------------------------------
# degenerate parity + dispatch validation
# --------------------------------------------------------------------------

def _problem(n=8, d=24):
    return make_quadratic(KEY, num_workers=n, dim=d, kappa=50.0,
                          coupling=0.0, num_regions=6, grad_noise=0.1,
                          hess_noise=0.1)


def test_scan_pods1_matches_flat_exactly():
    """``pods=1``: every exchange computes ``xbar == x`` so the consensus
    damping is the identity — the hierarchical program must reproduce
    the flat scan trajectory bit-for-bit (same PRNG stream, same
    reduction order)."""
    prob = _problem()
    pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
    kw = dict(num_rounds=6, num_regions=6, policy=pol)
    flat = repro.run(prob, KEY, **kw)
    hier = repro.run(prob, KEY, hierarchy="pods=1,period=2", **kw)
    assert hier.xs_pods.shape == (8, 1, prob.dim)
    np.testing.assert_array_equal(np.asarray(hier.xs),
                                  np.asarray(flat.xs))
    np.testing.assert_array_equal(np.asarray(hier.dist_sq),
                                  np.asarray(flat.dist_sq))
    np.testing.assert_array_equal(np.asarray(hier.comm_floats),
                                  np.asarray(flat.comm_floats))
    np.testing.assert_array_equal(np.asarray(hier.coverage),
                                  np.asarray(flat.coverage))


def test_hierarchy_dispatch_validation():
    prob = _problem(n=8)
    with pytest.raises(ValueError, match="divide evenly"):
        repro.run(prob, KEY, num_rounds=4, num_regions=6,
                  hierarchy="pods=3")
    with pytest.raises(ValueError, match="multiple of the"):
        repro.run(prob, KEY, num_rounds=5, num_regions=6,
                  hierarchy="pods=2,period=2")
    with pytest.raises(ValueError, match="no host-loop form"):
        repro.run(prob, KEY, engine="reference", num_rounds=4,
                  num_regions=6, hierarchy="pods=2,period=2")


# --------------------------------------------------------------------------
# pod_bytes period accounting
# --------------------------------------------------------------------------

def test_pod_bytes_period_accounting():
    """On a pod topology the flat engine's aggregate crosses the WAN
    every round (``4d`` modeled bytes); the hierarchical run pays only
    on every ``period``-th round, and int8 exchange compression shrinks
    that payload to ``d + 4`` bytes (coordinates + shared scale)."""
    d, T, period = 16, 8, 4
    prob = _problem(n=8, d=d)
    scen = make_scenario("geo-distributed", jax.random.PRNGKey(7), 8)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
    kw = dict(num_rounds=T, num_regions=6, policy=pol, cost=scen.cost)
    flat = repro.run(prob, KEY, **kw)
    np.testing.assert_allclose(np.asarray(flat.pod_bytes),
                               np.full(T, 4.0 * d))
    hier = repro.run(prob, KEY, hierarchy=f"pods=2,period={period}", **kw)
    want = np.zeros(T)
    want[period - 1::period] = 4.0 * d
    np.testing.assert_allclose(np.asarray(hier.pod_bytes), want)
    assert (float(np.asarray(hier.pod_bytes).mean())
            == float(np.asarray(flat.pod_bytes).mean()) / period)
    h8 = repro.run(prob, KEY,
                   hierarchy=f"pods=2,period={period},compression=int8",
                   **kw)
    want8 = np.zeros(T)
    want8[period - 1::period] = d + 4.0
    np.testing.assert_allclose(np.asarray(h8.pod_bytes), want8)


# --------------------------------------------------------------------------
# the pinned wall-clock win (acceptance bound)
# --------------------------------------------------------------------------

def test_hierarchical_time_to_target_pinned():
    """The regression-gated claim: on the uplink-asymmetric
    ``geo-distributed`` topology the hierarchical run reaches the target
    loss in <= 0.8x the flat-synchronous simulated wall-clock (same
    problem, seed and policy — mirrors ``bench_hierarchy``'s smoke
    configuration, which currently measures ~0.67x)."""
    dim, rounds, N = 32, 28, 16
    prob = make_quadratic(KEY, num_workers=N, dim=dim, kappa=100.0,
                          coupling=0.0, num_regions=8)
    scen = make_scenario("geo-distributed", jax.random.PRNGKey(101), N)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
    kw = dict(num_rounds=rounds, num_regions=8, lr=0.5, cost=scen.cost,
              policy=pol)
    res_f = repro.run(prob, KEY, **kw)
    res_h = repro.run(prob, KEY, hierarchy="pods=2,period=4", **kw)
    target = 1e-4 * float(res_f.dist_sq[0])
    t_f = time_to_target(res_f.dist_sq, res_f.round_time, target)
    t_h = time_to_target(res_h.dist_sq, res_h.round_time, target)
    assert np.isfinite(t_f) and np.isfinite(t_h)
    assert t_h <= 0.8 * t_f, (t_h, t_f)
    # and the win is a comm-schedule effect, not extra rounds of math:
    # both runs converge (to the shared optimum of the pod-aligned
    # quadratic), the hierarchical one just stops paying the WAN
    assert float(res_h.dist_sq[-1]) <= target


# --------------------------------------------------------------------------
# sharded engines: parity + compiled-HLO contract (slow, subprocess)
# --------------------------------------------------------------------------

def _run_subprocess(code: str, timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_PRELUDE = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
assert jax.device_count() == 8, jax.devices()
KEY = jax.random.PRNGKey(0)
"""


def _budget(hlo: dict, axis: str) -> dict:
    hits = [b for b in hlo["facts"]["budgets"] if b["axis"] == axis]
    assert len(hits) == 1, (axis, hlo["facts"]["budgets"])
    return hits[0]


@pytest.mark.slow
def test_hier_sharded_parity_and_contract_8dev():
    """Emulated pod meshes: the sharded engine on a ("pod","data") 2x4
    mesh and the sharded2d engine on the full ("pod","data","model")
    2x2x2 mesh must reproduce the scan oracle's hierarchical trajectory,
    bytes accounting and diagnostics — and ``verify_contract`` must
    prove, on the compiled partitioned HLO, that the inter-pod exchange
    psum carries multiplier ``E = rounds/period`` (int8 exchange: an s8
    payload) while exactly ONE intra-pod param-sized data psum per round
    survives.  The multiplier gap E vs T IS the
    inter-pod-bytes-reduced-by-period acceptance proof."""
    code = _PRELUDE + r"""
import repro
from repro.core import PolicyConfig, make_quadratic
from repro.analysis import engine_contract, verify_contract
from repro.launch.mesh import make_engine_mesh

D, T, PERIOD = 48, 6, 2
prob = make_quadratic(KEY, num_workers=8, dim=D, kappa=80.0,
                      coupling=0.0, num_regions=6, grad_noise=0.1,
                      hess_noise=0.1)
pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
opts = repro.RanlOptions(num_rounds=T, num_regions=6, policy=pol,
                         hierarchy=f"pods=2,period={PERIOD}")
mesh1d = make_mesh((2, 4), ('pod', 'data'))
mesh2d = make_engine_mesh(2, 2, pods=2)
assert mesh2d.axis_names == ('pod', 'data', 'model')

ref = repro.run(prob, KEY, engine="scan", options=opts)
out = {"parity": {}}
for name, engine, mesh in (("1d", "sharded", mesh1d),
                           ("2d", "sharded2d", mesh2d)):
    res = repro.run(prob, KEY, engine=engine, mesh=mesh, options=opts)
    out["parity"][name] = {
        "xs_err": float(jnp.abs(res.xs_pods - ref.xs_pods).max()),
        "comm_eq": bool((res.comm_floats == ref.comm_floats).all()),
        "cov_err": float(jnp.abs(res.coverage - ref.coverage).max()),
        "pod_bytes_eq": bool((res.pod_bytes == ref.pod_bytes).all()),
    }

out["hlo"] = {}
legs = (("1d", "sharded", mesh1d, (2, 4), ("pod", "data"), opts),
        ("2d", "sharded2d", mesh2d, (2, 2, 2),
         ("pod", "data", "model"), opts),
        ("1d_int8", "sharded", mesh1d, (2, 4), ("pod", "data"),
         opts.merged(hierarchy=f"pods=2,period={PERIOD},"
                               "compression=int8")))
for name, engine, mesh, shape, axes, o in legs:
    low = repro.lower(prob, KEY, engine=engine, mesh=mesh, options=o)
    comm, mem = engine_contract(engine, o, dim=D, num_workers=8,
                                mesh_shape=shape, mesh_axes=axes)
    out["hlo"][name] = verify_contract(low, comm, mem).to_json()
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    for name, r in res["parity"].items():
        assert r["xs_err"] <= 2e-5, (name, res)
        assert r["comm_eq"] and r["pod_bytes_eq"], (name, res)
        assert r["cov_err"] == 0.0, (name, res)
    T, period = 6, 2
    for name, hlo in res["hlo"].items():
        assert hlo["ok"], (name, hlo)
        data = _budget(hlo, "data")
        assert len(data["matched"]) == 1, (name, hlo)
        assert data["matched"][0]["multiplier"] == T, (name, hlo)
        pod = _budget(hlo, "pod")
        assert len(pod["matched"]) == 1, (name, hlo)
        assert pod["matched"][0]["multiplier"] == T // period, (name, hlo)
    # compressed exchange rides the WAN as int8 payload + f32 scale
    m = _budget(res["hlo"]["1d_int8"], "pod")["matched"][0]
    assert "s8" in m["operand_dtypes"], res["hlo"]["1d_int8"]
    assert m["operand_bytes"] < 4 * 48, m
