"""Device-parity harness: sharded engines vs the single-device oracles.

The multi-device tests force ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` in a subprocess (the parent's jax device count is locked
at first import — same pattern as ``test_subprocess_mini_dryrun``) and pin:

* sharded-engine trajectory parity (<= 1e-6; diagnostics exact)
  against the scan engine on 1/2/8-device ``("data",)`` meshes, dense and
  diag curvature — and ``overlap=True`` (the double-buffered loop)
  exactly equal to the sequential loop;
* sharded2d parity: the dense path (whole program sharded,
  init included — Newton–Schulz projection, no eigh) against
  the scan engine with ``projection="ns"``, the diag path against the
  diag oracle;
* batch-engine ``mesh=...`` parity against the unsharded batch engine,
  with the seed axis actually partitioned across devices;
* ``ranl_llm.train_step(mesh=...)`` parity against the single-device step
  on 1/2/8-device meshes (params to reduction-reorder tolerance);
* the communication claim, on compiled partitioned HLO via
  ``launch.hlo_analysis``: the core round loop issues exactly ONE
  param-sized all-reduce per round (plus a region-sized count reduce) —
  with and without overlap — and a full ``train_step`` moves one
  gradient-sized reduction pass total — the ``masked_aggregate``
  single-reduction comment as an invariant;
* the memory claim, now END TO END: with ``curvature="dense"`` on a 2-D
  mesh the largest per-device buffer across the WHOLE compiled program
  (init included) is the (d/n_model, d) panel — no replicated d×d
  buffer exists at any phase.
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
from repro.launch.mesh import make_mesh
from repro.core import PolicyConfig, make_quadratic

KEY = jax.random.PRNGKey(0)


def _run_subprocess(code: str, timeout: int = 560):
    """Run ``code`` (which must print a JSON dict as its last line) in a
    fresh interpreter so it can set XLA_FLAGS before importing jax."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
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


# --------------------------------------------------------------------------
# in-process checks (single real device)
# --------------------------------------------------------------------------

def test_sharded_single_device_mesh_matches_scan():
    """On a degenerate 1-device mesh the shard_map engine must reproduce
    the scan engine bit-for-bit (same PRNG stream, same reduction
    order) — and
    the double-buffered ``overlap=True`` loop must match the sequential
    one exactly (identical values, only the schedule moves)."""
    prob = make_quadratic(KEY, num_workers=8, dim=48, kappa=80.0,
                          coupling=0.0, num_regions=6, grad_noise=0.1,
                          hess_noise=0.1)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
    mesh = make_mesh((1,), ("data",))
    sh = repro.run(prob, KEY, engine="sharded", mesh=mesh, num_rounds=8,
                          num_regions=6, policy=pol)
    ref = repro.run(prob, KEY, num_rounds=8, num_regions=6, policy=pol)
    np.testing.assert_array_equal(np.asarray(sh.xs), np.asarray(ref.xs))
    np.testing.assert_array_equal(np.asarray(sh.comm_floats),
                                  np.asarray(ref.comm_floats))
    np.testing.assert_array_equal(np.asarray(sh.coverage),
                                  np.asarray(ref.coverage))
    assert sh.tau_star == ref.tau_star
    ov = repro.run(prob, KEY, engine="sharded", mesh=mesh, num_rounds=8,
                          num_regions=6, policy=pol, overlap=True)
    np.testing.assert_array_equal(np.asarray(ov.xs), np.asarray(sh.xs))
    np.testing.assert_array_equal(np.asarray(ov.comm_floats),
                                  np.asarray(sh.comm_floats))
    np.testing.assert_array_equal(np.asarray(ov.coverage),
                                  np.asarray(sh.coverage))
    assert ov.tau_star == sh.tau_star
    assert ov.tau_covered == sh.tau_covered


def test_sharded_mesh_validation_errors():
    prob = make_quadratic(KEY, num_workers=4, dim=16, kappa=10.0,
                          coupling=0.0, num_regions=4)
    no_data = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="data"):
        repro.run(prob, KEY, engine="sharded", mesh=no_data, num_rounds=2)
    with pytest.raises(ValueError, match="data"):
        repro.run(prob, jax.random.split(KEY, 2), engine="batch", num_rounds=2,
                       mesh=no_data)


def test_sharded2d_single_device_mesh_matches_scan():
    """On a degenerate 1x1 ("data","model") mesh the dimension-sharded
    engine must reproduce its single-device oracle (<= 1e-5): for dense
    that is now the scan engine with ``projection="ns"`` — the whole
    2-D dense
    program, init included, runs the matmul-only Newton–Schulz
    projection, never an eigh — and for diag the diag path.  Diagnostics
    exact, including the tau_star/tau_covered split under an adversarial
    staleness policy; ``overlap=True`` exactly equal to sequential."""
    prob = make_quadratic(KEY, num_workers=8, dim=48, kappa=80.0,
                          coupling=0.0, num_regions=6, grad_noise=0.1,
                          hess_noise=0.1)
    mesh = make_mesh((1, 1), ("data", "model"))
    for pol, curv in ((PolicyConfig(keep_prob=0.5, tau_star=1,
                                    heterogeneous=False), "dense"),
                      (PolicyConfig(name="staleness", stale_period=3),
                       "dense"),
                      (PolicyConfig(keep_prob=0.5, tau_star=1,
                                    heterogeneous=False), "diag")):
        kw = dict(num_rounds=8, num_regions=6, policy=pol, curvature=curv)
        sh = repro.run(prob, KEY, engine="sharded2d", mesh=mesh, **kw)
        ref = repro.run(prob, KEY, use_kernel=(curv == "diag"),
                       projection="ns" if curv == "dense" else "eigh",
                       **kw)
        assert np.abs(np.asarray(sh.xs) - np.asarray(ref.xs)).max() <= 1e-5
        np.testing.assert_array_equal(np.asarray(sh.comm_floats),
                                      np.asarray(ref.comm_floats))
        np.testing.assert_array_equal(np.asarray(sh.coverage),
                                      np.asarray(ref.coverage))
        assert sh.tau_star == ref.tau_star
        assert sh.tau_covered == ref.tau_covered
        if pol.name == "staleness":
            assert sh.tau_star == 0 and sh.tau_covered >= 1
        ov = repro.run(prob, KEY, engine="sharded2d", mesh=mesh, overlap=True, **kw)
        np.testing.assert_array_equal(np.asarray(ov.xs), np.asarray(sh.xs))
        np.testing.assert_array_equal(np.asarray(ov.comm_floats),
                                      np.asarray(sh.comm_floats))
        assert ov.tau_star == sh.tau_star


def test_sharded2d_mesh_validation_errors():
    prob = make_quadratic(KEY, num_workers=4, dim=16, kappa=10.0,
                          coupling=0.0, num_regions=4)
    with pytest.raises(ValueError, match="model"):
        repro.run(prob, KEY, engine="sharded2d", mesh=make_mesh((1,), ("data",)),
                           num_rounds=2)
    with pytest.raises(ValueError, match="data"):
        repro.run(prob, KEY, engine="sharded2d", mesh=make_mesh((1,), ("model",)),
                           num_rounds=2)


# --------------------------------------------------------------------------
# 8 emulated host devices (subprocess)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_scan_parity_and_hlo_one_allreduce():
    """Dense + diag parity on 1/2/8-device meshes, the worker-divisibility
    guard, and the one-param-sized-all-reduce-per-round HLO invariant."""
    code = _PRELUDE + r"""
import repro
from repro.core import PolicyConfig, make_quadratic

prob = make_quadratic(KEY, num_workers=8, dim=48, kappa=80.0, coupling=0.0,
                      num_regions=6, grad_noise=0.1, hess_noise=0.1)
pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
ref = repro.run(prob, KEY, num_rounds=12, num_regions=6, policy=pol)
out = {"parity": {}}
for ndev in (1, 2, 8):
    mesh = make_mesh((ndev,), ('data',))
    sh = repro.run(prob, KEY, engine="sharded", mesh=mesh, num_rounds=12,
                          num_regions=6, policy=pol)
    out["parity"][str(ndev)] = {
        "xs_err": float(np.abs(np.asarray(sh.xs)
                               - np.asarray(ref.xs)).max()),
        "cov_err": float(np.abs(np.asarray(sh.coverage)
                                - np.asarray(ref.coverage)).max()),
        "comm_eq": bool((np.asarray(sh.comm_floats)
                         == np.asarray(ref.comm_floats)).all()),
        "tau_eq": bool(sh.tau_star == ref.tau_star),
    }

mesh8 = make_mesh((8,), ('data',))
sh_d = repro.run(prob, KEY, engine="sharded", mesh=mesh8, num_rounds=12,
                        num_regions=6, policy=pol, curvature='diag')
ref_d = repro.run(prob, KEY, num_rounds=12, num_regions=6, policy=pol,
                 curvature='diag', use_kernel=False)
out["diag_err"] = float(np.abs(np.asarray(sh_d.xs)
                               - np.asarray(ref_d.xs)).max())

# workers must divide across devices
bad = make_quadratic(KEY, num_workers=6, dim=16, kappa=10.0, coupling=0.0)
try:
    repro.run(bad, KEY, engine="sharded", mesh=mesh8, num_rounds=2)
    out["divisibility_raises"] = False
except ValueError:
    out["divisibility_raises"] = True

# HLO: the declarative contract — exactly ONE param-sized data-axis
# all-reduce per scanned round, every other in-loop reduction under the
# small-payload ceiling (region counts / scalar comm) — via
# repro.analysis.verify_contract (the shared one-psum-per-round proof).
from repro.analysis import engine_contract, verify_contract
D, T = 512, 7
prob_h = make_quadratic(KEY, num_workers=8, dim=D, kappa=10.0,
                        coupling=0.0, num_regions=8)
opts = repro.RanlOptions(num_rounds=T, num_regions=8, policy=pol)
low = repro.lower(prob_h, KEY, engine="sharded", mesh=mesh8, options=opts)
comm, mem = engine_contract("sharded", opts, dim=D, num_workers=8,
                            mesh_shape=(8,), mesh_axes=("data",))
out["hlo"] = verify_contract(low, comm, mem).to_json()
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    for ndev, r in res["parity"].items():
        assert r["xs_err"] <= 1e-6, (ndev, res)
        assert r["cov_err"] == 0.0, (ndev, res)
        assert r["comm_eq"] and r["tau_eq"], (ndev, res)
    assert res["diag_err"] <= 1e-6, res
    assert res["divisibility_raises"], res
    hlo = res["hlo"]
    assert hlo["ok"], hlo
    # the contract budget (one param-sized psum x rounds) actually matched
    assert len(hlo["facts"]["budgets"][0]["matched"]) == 1, hlo


@pytest.mark.slow
def test_overlap_sharded_parity_and_hlo():
    """``overlap=True`` (the double-buffered round loop) on an 8-device
    ("data",) mesh: trajectories and diagnostics exactly equal to the
    sequential loop — the pipelining only moves x-independent work into
    the param-psum window, it never changes a value — and the compiled
    HLO still issues exactly ONE param-sized all-reduce per round."""
    code = _PRELUDE + r"""
import repro
from repro.core import PolicyConfig, make_quadratic

prob = make_quadratic(KEY, num_workers=8, dim=48, kappa=80.0, coupling=0.0,
                      num_regions=6, grad_noise=0.1, hess_noise=0.1)
pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
mesh8 = make_mesh((8,), ('data',))
out = {}
kw = dict(num_rounds=12, num_regions=6, policy=pol)
seq = repro.run(prob, KEY, engine="sharded", mesh=mesh8, **kw)
ov = repro.run(prob, KEY, engine="sharded", mesh=mesh8, overlap=True, **kw)
out["xs_eq"] = bool((np.asarray(seq.xs) == np.asarray(ov.xs)).all())
out["comm_eq"] = bool((np.asarray(seq.comm_floats)
                       == np.asarray(ov.comm_floats)).all())
out["cov_eq"] = bool((np.asarray(seq.coverage)
                      == np.asarray(ov.coverage)).all())
out["tau_eq"] = bool(seq.tau_star == ov.tau_star
                     and seq.tau_covered == ov.tau_covered)
seq_d = repro.run(prob, KEY, engine="sharded", mesh=mesh8, curvature='diag', **kw)
ov_d = repro.run(prob, KEY, engine="sharded", mesh=mesh8, curvature='diag',
                        overlap=True, **kw)
out["diag_xs_eq"] = bool((np.asarray(seq_d.xs)
                          == np.asarray(ov_d.xs)).all())

# HLO: pipelining shifts the coverage-count psum across the iteration
# boundary but never adds a param-sized collective — the overlap run must
# satisfy the SAME contract as the sequential one (the param-psum window
# carries PARAM_SLACK for the count psum riding the combined all-reduce)
from repro.analysis import engine_contract, verify_contract
D, T = 512, 7
prob_h = make_quadratic(KEY, num_workers=8, dim=D, kappa=10.0,
                        coupling=0.0, num_regions=8)
opts = repro.RanlOptions(num_rounds=T, num_regions=8, policy=pol,
                         overlap=True)
low = repro.lower(prob_h, KEY, engine="sharded", mesh=mesh8, options=opts)
comm, mem = engine_contract("sharded", opts, dim=D, num_workers=8,
                            mesh_shape=(8,), mesh_axes=("data",))
out["hlo"] = verify_contract(low, comm, mem).to_json()
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    assert res["xs_eq"] and res["comm_eq"] and res["cov_eq"] \
        and res["tau_eq"], res
    assert res["diag_xs_eq"], res
    hlo = res["hlo"]
    assert hlo["ok"], hlo
    assert len(hlo["facts"]["budgets"][0]["matched"]) == 1, hlo


_PRELUDE4 = _PRELUDE.replace("device_count=8", "device_count=4").replace(
    "jax.device_count() == 8", "jax.device_count() == 4")


@pytest.mark.slow
def test_sharded2d_parity_and_hlo_memory_claims():
    """Dimension-sharded engine on emulated 2-D meshes:

    * trajectory parity (<= 1e-5) on 2x2 and 1x4 ("data","model") meshes
      vs the matching single-device oracle — scan with ``projection="ns"``
      for dense (the whole sharded dense program, init included, runs the
      Newton-Schulz projection, never an eigh), the diag oracle for diag
      (the 1x4 run exercises the fused Pallas kernel on local d-slices);
    * ``overlap=True`` exactly equal to the sequential loop on the 2x2
      mesh, both curvatures;
    * worker/dim divisibility guards;
    * the compiled-HLO memory + communication claims on a 2x2 mesh, for
      the WHOLE dense program (init included, overlap on and off):
      exactly ONE data-axis param-SHARD all-reduce (d/n_model floats) per
      round, model-axis collectives bounded by the NS panel products
      (never a d x d payload), no in-loop gather-style collectives, and
      no single per-device buffer above the (d/n_model, d) panel (+ block
      slack) ANYWHERE in the program — the last replicated O(d^2) is gone.
    """
    code = _PRELUDE4 + r"""
import repro
from repro.core import PolicyConfig, make_quadratic
from repro.launch.mesh import make_engine_mesh

prob = make_quadratic(KEY, num_workers=8, dim=48, kappa=80.0, coupling=0.0,
                      num_regions=6, grad_noise=0.1, hess_noise=0.1)
pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
out = {"parity": {}, "overlap": {}}
for curv in ("dense", "diag"):
    kw = dict(num_rounds=12, num_regions=6, policy=pol, curvature=curv)
    ref = repro.run(prob, KEY, use_kernel=False,
                   projection="ns" if curv == "dense" else "eigh", **kw)
    for shape in ((2, 2), (1, 4)):
        mesh = make_engine_mesh(*shape)
        sh = repro.run(prob, KEY, engine="sharded2d", mesh=mesh, **kw)
        out["parity"]["%s_%dx%d" % ((curv,) + shape)] = {
            "xs_err": float(np.abs(np.asarray(sh.xs)
                                   - np.asarray(ref.xs)).max()),
            "cov_err": float(np.abs(np.asarray(sh.coverage)
                                    - np.asarray(ref.coverage)).max()),
            "comm_eq": bool((np.asarray(sh.comm_floats)
                             == np.asarray(ref.comm_floats)).all()),
            "tau_eq": bool(sh.tau_star == ref.tau_star
                           and sh.tau_covered == ref.tau_covered),
        }
        if shape == (2, 2):
            ov = repro.run(prob, KEY, engine="sharded2d", mesh=mesh, overlap=True,
                                    **kw)
            out["overlap"][curv] = {
                "xs_eq": bool((np.asarray(ov.xs)
                               == np.asarray(sh.xs)).all()),
                "comm_eq": bool((np.asarray(ov.comm_floats)
                                 == np.asarray(sh.comm_floats)).all()),
                "tau_eq": bool(ov.tau_star == sh.tau_star),
            }

# divisibility guards
mesh22 = make_engine_mesh(2, 2)
bad_w = make_quadratic(KEY, num_workers=3, dim=16, kappa=10.0, coupling=0.0)
bad_d = make_quadratic(KEY, num_workers=4, dim=15, kappa=10.0, coupling=0.0)
out["bad_workers_raises"] = out["bad_dim_raises"] = False
try:
    repro.run(bad_w, KEY, engine="sharded2d", mesh=mesh22, num_rounds=2)
except ValueError:
    out["bad_workers_raises"] = True
try:
    repro.run(bad_d, KEY, engine="sharded2d", mesh=mesh22, num_rounds=2)
except ValueError:
    out["bad_dim_raises"] = True
from repro.core import project_psd_sharded
out["proj_bad_dim_raises"] = False
try:
    project_psd_sharded(jnp.zeros((5, 5)), 0.1, mesh=mesh22)
except ValueError:
    out["proj_bad_dim_raises"] = True

# HLO memory + communication claims (compile only, d=512 on a 2x2 mesh:
# param shard p = 256; N=2 so the per-device problem shard stays < d^2).
# The dense lowering covers the WHOLE program — sharded mean-Hessian
# accumulation, NS projection (NS_IT iterations, panel-product psums),
# blocked factorization, first Newton step, and the round loop.  The
# declarative sharded2d contract states all of it: one data-axis
# param-SHARD psum per round, model-axis budgets bounded by d floats
# (round loop) / two panels (NS loop), no in-loop gathers, every in-loop
# collective attributed to a mesh axis, and a peak per-device buffer of
# one (d/n_model, d) panel — no replicated d x d buffer anywhere.
from repro.analysis import engine_contract, verify_contract
D, T, NS_IT = 512, 7, 12
prob_h = make_quadratic(KEY, num_workers=2, dim=D, kappa=10.0,
                        coupling=0.0, num_regions=8)
out["hlo"] = {}
for leg, ov in (("seq", False), ("overlap", True)):
    opts = repro.RanlOptions(num_rounds=T, num_regions=8, policy=pol,
                             ns_iters=NS_IT, overlap=ov)
    low = repro.lower(prob_h, KEY, engine="sharded2d", mesh=mesh22,
                      options=opts)
    comm, mem = engine_contract("sharded2d", opts, dim=D, num_workers=2,
                                mesh_shape=(2, 2),
                                mesh_axes=("data", "model"))
    out["hlo"][leg] = verify_contract(low, comm, mem).to_json()
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    for name, r in res["parity"].items():
        assert r["xs_err"] <= 1e-5, (name, res)
        assert r["cov_err"] == 0.0, (name, res)
        assert r["comm_eq"] and r["tau_eq"], (name, res)
    for curv, r in res["overlap"].items():
        assert r["xs_eq"] and r["comm_eq"] and r["tau_eq"], (curv, res)
    assert res["bad_workers_raises"] and res["bad_dim_raises"], res
    assert res["proj_bad_dim_raises"], res
    D = 512  # matches the subprocess HLO problem dim
    for leg in ("seq", "overlap"):
        hlo = res["hlo"][leg]
        assert hlo["ok"], (leg, hlo)
        budgets = hlo["facts"]["budgets"]
        # the data-axis param-shard psum matched exactly once, and the
        # optional model-axis budgets (solve broadcasts, NS panel
        # products) are actually exercised — this is a positive claim,
        # not just an upper bound
        assert len(budgets[0]["matched"]) == 1, (leg, hlo)
        assert budgets[1]["matched"], (leg, hlo)   # round-loop model psums
        assert budgets[2]["matched"], (leg, hlo)   # NS-loop panel psums
        # memory window [panel, panel + slack] sits far below d x d
        assert hlo["facts"]["max_array_bytes"] < D * D * 4, (leg, hlo)


@pytest.mark.slow
def test_sharded_batch_parity_and_placement():
    """Batch engine with mesh=... matches the unsharded batch engine and
    actually spreads the seed axis across the mesh devices."""
    code = _PRELUDE + r"""
import repro
from repro.core import PolicyConfig, make_quadratic

prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=50.0, coupling=0.0,
                      num_regions=4, grad_noise=0.1)
pol = PolicyConfig(keep_prob=0.5, tau_star=1)
keys = jax.random.split(KEY, 8)
ref = repro.run(prob, keys, engine="batch", num_rounds=10, num_regions=4, policy=pol)
out = {}
for ndev in (1, 2, 8):
    mesh = make_mesh((ndev,), ('data',))
    bat = repro.run(prob, keys, engine="batch", num_rounds=10, num_regions=4,
                         policy=pol, mesh=mesh)
    out[str(ndev)] = {
        "xs_err": float(np.abs(np.asarray(bat.xs)
                               - np.asarray(ref.xs)).max()),
        "comm_eq": bool((np.asarray(bat.comm_floats)
                         == np.asarray(ref.comm_floats)).all()),
        "tau_eq": bool((np.asarray(bat.tau_star)
                        == np.asarray(ref.tau_star)).all()),
        "n_devices_used": len(bat.xs.sharding.device_set),
    }
try:
    repro.run(prob, jax.random.split(KEY, 6), engine="batch", num_rounds=2,
                   mesh=make_mesh((8,), ('data',)))
    out["divisibility_raises"] = False
except ValueError:
    out["divisibility_raises"] = True
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    for ndev in ("1", "2", "8"):
        r = res[ndev]
        assert r["xs_err"] <= 1e-6, (ndev, res)
        assert r["comm_eq"] and r["tau_eq"], (ndev, res)
        assert r["n_devices_used"] == int(ndev), (ndev, res)
    assert res["divisibility_raises"], res


@pytest.mark.slow
def test_train_step_sharded_parity_and_single_reduction_hlo():
    """ranl_llm.train_step with a mesh matches the single-device step on
    1/2/8-device meshes, and its compiled HLO moves exactly one
    gradient-sized all-reduce pass (masked_aggregate's claim)."""
    code = _PRELUDE + r"""
from functools import partial
from repro.configs import get_config, smoke_variant
from repro.data import make_batch
from repro.models import init_model, lm_loss
from repro.optim import RanlLLMConfig, init_state, train_step

cfg = smoke_variant(get_config('phi4-mini-3.8b'))
params = init_model(cfg, KEY)
loss_fn = lambda p, b: lm_loss(p, b, cfg, q_chunk=16, kv_chunk=16)
batch = make_batch(cfg, KEY, 8, 32, pattern='bigram')
rcfg = RanlLLMConfig(num_workers=8)
state = init_state(params, loss_fn, batch, rcfg, KEY)
ref = jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg))
p1, s1, m1 = ref(params, state, batch, KEY)
out = {"parity": {}}
for ndev in (1, 2, 8):
    mesh = make_mesh((ndev,), ('data',))
    sh = jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg, mesh=mesh))
    p2, s2, m2 = sh(params, state, batch, KEY)
    perr = prel = 0.0
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        a = np.asarray(a, np.float32); b = np.asarray(b, np.float32)
        perr = max(perr, float(np.abs(a - b).max()))
        prel = max(prel, float((np.abs(a - b)
                                / (np.abs(a) + 1e-3)).max()))
    out["parity"][str(ndev)] = {
        "param_abs_err": perr, "param_rel_err": prel,
        "loss_err": abs(float(m1['loss']) - float(m2['loss'])),
        "coverage_eq": float(m1['coverage']) == float(m2['coverage']),
        "uplink_eq": float(m1['uplink_frac']) == float(m2['uplink_frac']),
        "step_eq": int(s2['step']) == int(s1['step']),
    }

# single-reduction invariant on the compiled 8-device step: total
# all-reduce traffic == one fp32 pass over the gradients (+ scalar
# epsilon for the per-leaf counts / trust-ratio / metric reductions) —
# stated as an aggregate-bytes contract (the window applies to the SUM
# of every matching all-reduce, not per-collective)
from repro.analysis import CollectiveBudget, CommContract, verify_contract
mesh8 = make_mesh((8,), ('data',))
sh8 = jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg, mesh=mesh8))
grad_bytes = sum(l.size * 4 for l in jax.tree.leaves(params))
comm = CommContract(
    mesh_axes=('data',), mesh_shape=(8,), rounds=1,
    budgets=(CollectiveBudget(axis='data', count=None,
                              min_bytes=grad_bytes,
                              max_bytes=grad_bytes + 64 * 1024,
                              multipliers=(1,)),),
    small_max_bytes=1 << 30, allow_inloop_gather=True,
    in_loop_only=False, require_classified=False, aggregate_bytes=True)
rep = verify_contract(sh8.lower(params, state, batch, KEY), comm)
out["hlo"] = rep.to_json()
out["grad_bytes"] = grad_bytes
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    for ndev, r in res["parity"].items():
        # reduction-reorder tolerance: worker-axis sums are partitioned
        assert r["param_abs_err"] <= 1e-5, (ndev, res)
        assert r["param_rel_err"] <= 3e-4, (ndev, res)
        assert r["loss_err"] <= 1e-5, (ndev, res)
        assert r["coverage_eq"] and r["uplink_eq"] and r["step_eq"], \
            (ndev, res)
    hlo = res["hlo"]
    assert hlo["ok"], hlo
    assert hlo["facts"]["budgets"][0]["matched"], hlo
