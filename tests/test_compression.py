"""Compressed curvature & gradient communication — plus the
time-to-accuracy and validation bugfix regressions that ride along.

Pins, in order:

* compressor round-trip bounds (hypothesis properties): int8 absmax
  error <= half a quantization step, bf16 relative error <= 2^-8, top-k
  keeps at most ``k`` regions verbatim and zeroes the rest;
* ``parse_compression`` / ``RanlOptions`` / ``PolicyConfig``
  construction-time validation, and the ``hessian_rank`` engine
  rejections (``reference``, ``sharded2d``);
* the ``uplink_bytes`` wire model (the single source of
  ``RanlResult.comm_bytes`` and the CostModel uplink charge);
* ``compression=None`` is bit-exactness rail: the static ``comp is
  None`` branch compiles the historical uncompressed loop on EVERY
  engine (cross-engine trajectory parity + ``comm_bytes ==
  4 * comm_floats``);
* error-feedback convergence: int8/bf16/top-k runs land within a
  pinned factor of the uncompressed run on the same quadratic, with
  strictly smaller metered bytes — and int8 reaches the target in LESS
  simulated wall-clock on the finite-uplink straggler scenario
  (``pareto-stragglers:alpha=1.2,bw=1``, the ``bench_compression``
  claim);
* the ``time_to_target`` record_every fix: thinned traces are charged
  the cumulative time through THEIR rounds (the historical indexing
  scored them against the wrong rounds' clock), and a trace whose
  length matches neither schedule raises;
* ``chol_rank1_update`` algebra and ``hessian_rank=d`` reproducing the
  dense init on the scan engine;
* (slow, subprocess, 8 emulated devices) the compiled-HLO claim: the
  int8 sharded loop still issues exactly ONE in-loop param-shard
  all-reduce per round, its operand is ``s8``, and the payload is
  >= 3.5x smaller than the uncompressed loop's f32 operand.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.core import PolicyConfig, make_quadratic
from repro.core.compression import (
    CompressionSpec,
    chol_rank1_update,
    compress_rows,
    parse_compression,
    uplink_bytes,
)
from repro.hetero import make_scenario, time_to_target

KEY = jax.random.PRNGKey(0)


def _problem(num_workers=8, dim=32, num_regions=4):
    return make_quadratic(KEY, num_workers=num_workers, dim=dim,
                          kappa=50.0, coupling=0.0,
                          num_regions=num_regions)


def _mesh1d():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))


def _mesh2d():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))


_POL = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)


# --------------------------------------------------------------------------
# parsing / construction-time validation
# --------------------------------------------------------------------------

def test_parse_compression_specs():
    assert parse_compression(None) is None
    assert parse_compression("int8") == CompressionSpec(kind="int8")
    assert parse_compression("bf16") == CompressionSpec(kind="bf16")
    spec = parse_compression("topk:3")
    assert spec.kind == "topk" and spec.k == 3
    assert parse_compression(spec) is spec          # passthrough


@pytest.mark.parametrize("bad", ["gzip", "topk:0", "topk:-1", "topk:x",
                                 "topk:", "int4"])
def test_parse_compression_rejects(bad):
    with pytest.raises(ValueError, match="compression"):
        parse_compression(bad)


def test_options_validate_compression_and_rank():
    with pytest.raises(ValueError, match="compression"):
        repro.RanlOptions(compression="nope")
    with pytest.raises(ValueError, match="hessian_rank"):
        repro.RanlOptions(hessian_rank=0)
    opts = repro.RanlOptions(compression="topk:2", hessian_rank=4)
    spec = opts.compression_spec()
    assert spec.kind == "topk" and spec.k == 2
    assert repro.RanlOptions().compression_spec() is None


def test_policy_config_validates_at_construction():
    with pytest.raises(ValueError, match="keep_prob"):
        PolicyConfig(keep_prob=0.0)
    with pytest.raises(ValueError, match="keep_prob"):
        PolicyConfig(keep_prob=1.5)
    with pytest.raises(ValueError, match="keep_k"):
        PolicyConfig(keep_k=0)
    with pytest.raises(ValueError, match="stale_period"):
        PolicyConfig(stale_period=-1)
    with pytest.raises(ValueError, match="tau_star"):
        PolicyConfig(tau_star=-1)
    PolicyConfig(keep_prob=1.0, keep_k=1, stale_period=0, tau_star=0)


def test_hessian_rank_rejected_on_reference_and_sharded2d():
    prob = _problem()
    with pytest.raises(ValueError, match="hessian_rank"):
        repro.run(prob, KEY, engine="reference", num_rounds=2,
                  hessian_rank=4)
    with pytest.raises(ValueError, match="hessian_rank"):
        repro.run(prob, KEY, engine="sharded2d", mesh=_mesh2d(),
                  num_rounds=2, hessian_rank=4)


# --------------------------------------------------------------------------
# compressor round-trip bounds (hypothesis properties)
# --------------------------------------------------------------------------

def _rows(seed, n, d, scale):
    key = jax.random.PRNGKey(seed)
    return scale * jax.random.normal(key, (n, d), jnp.float32)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(4, 48),
       st.floats(1e-3, 1e3))
def test_int8_roundtrip_bound(seed, n, d, scale):
    """Per-row absmax quantization: error <= half a step everywhere."""
    Y = _rows(seed, n, d, scale)
    rids = jnp.zeros((d,), jnp.int32)
    R = compress_rows(CompressionSpec(kind="int8"), Y, rids, 1)
    step = np.maximum(np.abs(np.asarray(Y)).max(axis=-1, keepdims=True),
                      1e-30) / 127.0
    err = np.abs(np.asarray(Y) - np.asarray(R))
    assert (err <= 0.5 * step + 1e-6 * step).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(4, 48),
       st.floats(1e-3, 1e3))
def test_bf16_roundtrip_bound(seed, n, d, scale):
    """bfloat16 keeps 8 significand bits: relative error <= 2^-8."""
    Y = _rows(seed, n, d, scale)
    rids = jnp.zeros((d,), jnp.int32)
    R = compress_rows(CompressionSpec(kind="bf16"), Y, rids, 1)
    err = np.abs(np.asarray(Y) - np.asarray(R))
    assert (err <= np.abs(np.asarray(Y)) * 2.0 ** -8 + 1e-30).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 4))
def test_topk_keeps_heaviest_regions_verbatim(seed, n, k):
    """Top-k: kept coordinates pass through exactly, dropped regions go
    to zero, at most k regions survive, and every surviving region's
    energy >= every dropped (nonzero) region's energy."""
    Q, per = 6, 5
    d = Q * per
    rids = jnp.repeat(jnp.arange(Q), per)
    Y = _rows(seed, n, d, 1.0)
    R = np.asarray(compress_rows(CompressionSpec(kind="topk", k=k), Y,
                                 rids, Q))
    Yn = np.asarray(Y)
    rn = np.asarray(rids)
    for i in range(n):
        energy = np.array([np.sum(Yn[i, rn == q] ** 2)
                           for q in range(Q)])
        kept_q = sorted({int(q) for q in rn
                         if R[i, rn == q].any()})
        assert len(kept_q) <= k
        for q in range(Q):
            sel = rn == q
            if q in kept_q:
                np.testing.assert_array_equal(R[i, sel], Yn[i, sel])
            else:
                assert (R[i, sel] == 0).all()
                assert all(energy[q] <= energy[p] + 1e-12
                           for p in kept_q)


# --------------------------------------------------------------------------
# the uplink wire model
# --------------------------------------------------------------------------

def test_uplink_bytes_wire_model():
    M = jnp.array([[1, 1, 0], [0, 1, 0], [0, 0, 0]], bool)   # (N=3, Q=3)
    sizes = jnp.array([10, 20, 30], jnp.int32)
    work = np.array([30.0, 20.0, 0.0])                       # kept coords
    np.testing.assert_array_equal(
        np.asarray(uplink_bytes(None, M, sizes)), 4.0 * work)
    np.testing.assert_array_equal(
        np.asarray(uplink_bytes(CompressionSpec(kind="int8"), M, sizes)),
        np.array([34.0, 24.0, 0.0]))                         # w + scale
    np.testing.assert_array_equal(
        np.asarray(uplink_bytes(CompressionSpec(kind="bf16"), M, sizes)),
        2.0 * work)
    got = np.asarray(uplink_bytes(CompressionSpec(kind="topk", k=1), M,
                                  sizes))
    # largest trained region (20 for both participants) + 4B metadata
    np.testing.assert_array_equal(got, np.array([84.0, 84.0, 0.0]))


# --------------------------------------------------------------------------
# compression=None is the bit-exactness rail on every engine
# --------------------------------------------------------------------------

def test_compression_none_bit_exact_across_engines():
    """With compression=None the static branch compiles the historical
    uncompressed loop: every engine still agrees with the scan engine,
    and the byte meter is exactly 4x the float meter."""
    prob = _problem()
    opts = repro.RanlOptions(num_rounds=8, num_regions=4, policy=_POL,
                             compression=None)
    ref = repro.run(prob, KEY, engine="scan", options=opts)
    assert np.isfinite(np.asarray(ref.dist_sq)).all()
    np.testing.assert_array_equal(np.asarray(ref.comm_bytes),
                                  4.0 * np.asarray(ref.comm_floats))
    for engine, kw in [("reference", {}), ("sharded", {"mesh": _mesh1d()}),
                       ("sharded2d", {"mesh": _mesh2d()})]:
        res = repro.run(prob, KEY, engine=engine, options=opts, **kw)
        np.testing.assert_allclose(np.asarray(res.xs),
                                   np.asarray(ref.xs), atol=2e-5,
                                   err_msg=engine)
        np.testing.assert_array_equal(np.asarray(res.comm_bytes),
                                      4.0 * np.asarray(res.comm_floats),
                                      err_msg=engine)
    batch = repro.run(prob, KEY[None], engine="batch", options=opts)
    np.testing.assert_allclose(np.asarray(batch.xs)[0],
                               np.asarray(ref.xs), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(batch.comm_bytes)[0],
                                  np.asarray(ref.comm_bytes))


# --------------------------------------------------------------------------
# error-feedback convergence + metered bytes
# --------------------------------------------------------------------------

def test_error_feedback_convergence_and_bytes():
    """Compressed runs track the uncompressed one (EF absorbs the lossy
    uplink) and meter strictly fewer bytes for the same floats."""
    prob = _problem()
    base = repro.RanlOptions(num_rounds=60, lr=0.5, num_regions=4,
                             policy=_POL)
    res = {c: repro.run(prob, KEY, engine="scan",
                        options=base.merged(compression=c))
           for c in (None, "int8", "bf16", "topk:2")}
    d_none = float(res[None].dist_sq[-1])
    assert np.isfinite(d_none)
    # calibrated on the pinned problem: int8/bf16 land within 5%,
    # top-k (which drops whole regions per round) within 50%
    assert float(res["int8"].dist_sq[-1]) <= 1.05 * d_none
    assert float(res["bf16"].dist_sq[-1]) <= 1.05 * d_none
    assert float(res["topk:2"].dist_sq[-1]) <= 1.5 * d_none
    b_none = float(np.asarray(res[None].comm_bytes).sum())
    for c, bound in (("int8", 0.5), ("bf16", 0.5 + 1e-9),
                     ("topk:2", 1.0)):
        assert float(np.asarray(res[c].comm_bytes).sum()) < bound * b_none, c
        np.testing.assert_array_equal(np.asarray(res[c].comm_floats),
                                      np.asarray(res[None].comm_floats))


def test_compressed_quorum_path_converges():
    """compressed_quorum_aggregate: int8 on-time uplinks + uncompressed
    late folds still converge alongside the uncompressed quorum run."""
    prob = _problem()
    base = repro.RanlOptions(num_rounds=60, lr=0.5, num_regions=4,
                             policy=_POL, quorum=0.75, quorum_tau=1)
    d = {c: float(repro.run(prob, KEY, engine="scan",
                            options=base.merged(compression=c))
                  .dist_sq[-1])
         for c in (None, "int8")}
    assert np.isfinite(d[None]) and np.isfinite(d["int8"])
    assert d["int8"] <= 1.1 * d[None]


def test_int8_beats_f32_on_finite_uplink_stragglers():
    """The bench_compression claim as a regression test: on the
    finite-bandwidth pareto-stragglers scenario the int8 run reaches the
    pinned target loss in LESS simulated wall-clock than f32."""
    N = 16
    prob = make_quadratic(KEY, num_workers=N, dim=32, kappa=100.0,
                          coupling=0.0, num_regions=8)
    scen = make_scenario("pareto-stragglers:alpha=1.2,bw=1",
                         jax.random.PRNGKey(101), N)
    kw = dict(num_rounds=30, num_regions=8, lr=0.5, cost=scen.cost,
              policy=_POL)
    t = {}
    for comp in (None, "int8"):
        r = repro.run(prob, KEY, compression=comp, **kw)
        target = 1e-4 * float(r.dist_sq[0])
        t[comp] = time_to_target(r.dist_sq, r.round_time, target)
    assert np.isfinite(t["int8"]) and np.isfinite(t[None])
    assert t["int8"] < t[None], t


# --------------------------------------------------------------------------
# time_to_target x record_every (the time-to-accuracy bugfix)
# --------------------------------------------------------------------------

def test_time_to_target_full_trace():
    trace = [1.0, 0.9, 0.8, 0.3, 0.1]          # x0, x1, rounds 1..3
    times = [10.0, 100.0, 1000.0]
    assert time_to_target(trace, times, 0.8) == 10.0
    assert time_to_target(trace, times, 0.3) == 110.0
    assert time_to_target(trace, times, 0.05) == float("inf")


def test_time_to_target_record_every_charges_kept_rounds():
    """T=7, record_every=3 keeps rounds {3, 6, 7}: the kept iterates are
    charged the cumulative time through THEIR rounds — the historical
    indexing would have charged rounds 1..3."""
    times = [1.0] * 7
    trace = [1.0, 0.9, 0.8, 0.05, 0.04]        # x0, x1, rounds 3, 6, 7
    assert time_to_target(trace, times, 0.8, record_every=3) == 3.0
    assert time_to_target(trace, times, 0.05, record_every=3) == 6.0
    assert time_to_target(trace, times, 0.04, record_every=3) == 7.0
    assert time_to_target(trace, times, 0.01, record_every=3) == float("inf")


def test_time_to_target_rejects_mismatched_trace():
    with pytest.raises(ValueError, match="does not match"):
        time_to_target([1.0, 0.9, 0.8], [1.0] * 7, 0.5, record_every=3)
    with pytest.raises(ValueError, match="does not match"):
        time_to_target([1.0] * 9, [1.0] * 7, 0.5, record_every=3)


def test_time_to_target_accepts_engine_thinned_traces():
    """A real thinned run: the kept schedule for T=12, k=5 is rounds
    {5, 10, 12}, so any returned time must be the cumulative clock
    through one of THOSE rounds (the historical indexing charged the
    thinned trace rounds 1..3's clock) — and scoring the thinned trace
    without record_every= raises instead of silently mis-charging."""
    prob = _problem()
    thin = repro.run(prob, KEY, num_rounds=12, num_regions=4,
                     policy=_POL, record_every=5)
    target = float(np.asarray(thin.dist_sq)[-1])   # met by construction
    t = time_to_target(thin.dist_sq, thin.round_time, target,
                       record_every=5)
    times = np.cumsum(np.asarray(thin.round_time, np.float64))
    assert t in (times[4], times[9], times[11]), (t, times)
    with pytest.raises(ValueError, match="does not match"):
        time_to_target(thin.dist_sq, thin.round_time, target)


# --------------------------------------------------------------------------
# low-rank [H]_mu running update
# --------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 12),
       st.floats(0.0, 10.0))
def test_chol_rank1_update_algebra(seed, n, alpha):
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (n, n), jnp.float32)
    L = jnp.linalg.cholesky(A @ A.T + jnp.eye(n))
    u = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    L2 = chol_rank1_update(L, u, alpha)
    np.testing.assert_allclose(
        np.asarray(L2 @ L2.T),
        np.asarray(L @ L.T + alpha * jnp.outer(u, u)),
        atol=1e-3, rtol=1e-4)
    # negative alpha clamps to zero (no downdating arises here)
    L3 = chol_rank1_update(L, u, -1.0)
    np.testing.assert_allclose(np.asarray(L3 @ L3.T),
                               np.asarray(L @ L.T), atol=1e-4, rtol=1e-5)


def test_hessian_rank_full_reproduces_dense_init():
    """rank = d folds every eigenpair: the running low-rank init must
    reproduce the dense init's trajectory on the scan engine."""
    prob = _problem(dim=32)
    base = repro.RanlOptions(num_rounds=20, lr=0.5, num_regions=4,
                             policy=_POL)
    dense = repro.run(prob, KEY, engine="scan", options=base)
    lowr = repro.run(prob, KEY, engine="scan",
                     options=base.merged(hessian_rank=32))
    np.testing.assert_allclose(np.asarray(lowr.dist_sq),
                               np.asarray(dense.dist_sq), rtol=1e-3,
                               atol=1e-8)


# --------------------------------------------------------------------------
# the compiled-HLO payload claim (slow, subprocess, 8 emulated devices)
# --------------------------------------------------------------------------

def _run_subprocess(code: str, timeout: int = 560):
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


@pytest.mark.slow
def test_hlo_int8_one_param_psum_with_smaller_payload():
    """On the 8-device sharded engine the int8 loop still issues exactly
    ONE in-loop param-shard all-reduce per round, its operand dtype is
    s8, and its payload is >= 3.5x smaller than the f32 loop's (the
    remaining in-loop reductions are the region counts and the tiny f32
    shared-scale pmax)."""
    code = _PRELUDE + r"""
import repro
from repro.core import PolicyConfig, make_quadratic
from repro.analysis import engine_contract, verify_contract

D, T = 512, 7
prob = make_quadratic(KEY, num_workers=8, dim=D, kappa=10.0,
                      coupling=0.0, num_regions=8)
pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=False)
mesh = make_mesh((8,), ('data',))

out = {}
for comp, tag in ((None, 'none'), ('int8', 'int8')):
    opts = repro.RanlOptions(num_rounds=T, num_regions=8, policy=pol,
                             compression=comp)
    low = repro.lower(prob, KEY, engine="sharded", mesh=mesh,
                      options=opts)
    # the int8 contract pins the payload dtype to s8 and shrinks the
    # window to ~d bytes; the pmax shared scale + region counts must
    # stay under the small-payload ceiling
    comm, mem = engine_contract("sharded", opts, dim=D, num_workers=8,
                                mesh_shape=(8,), mesh_axes=("data",))
    out[tag] = verify_contract(low, comm, mem).to_json()

# parity while we're here: int8 on 8 devices runs and converges
res = repro.run(prob, KEY, engine="sharded", mesh=mesh, num_rounds=T,
                num_regions=8, policy=pol, compression='int8')
out["int8_final_finite"] = bool(np.isfinite(float(res.dist_sq[-1])))
out["int8_bytes_lt_none"] = bool(
    float(np.asarray(res.comm_bytes).sum())
    < 4.0 * float(np.asarray(res.comm_floats).sum()))
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    wire = {}
    for tag in ("none", "int8"):
        assert res[tag]["ok"], res[tag]
        matched = res[tag]["facts"]["budgets"][0]["matched"]
        assert len(matched) == 1, res[tag]
        wire[tag] = matched[0]
    assert "s8" in wire["int8"]["operand_dtypes"], res
    # the compressed wire payload is >= 3.5x smaller than the f32 one
    ratio = wire["none"]["operand_bytes"] / wire["int8"]["operand_bytes"]
    assert ratio >= 3.5, (ratio, res)
    assert res["int8_final_finite"] and res["int8_bytes_lt_none"], res
