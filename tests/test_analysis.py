"""Static verification subsystem (``repro.analysis``) tests.

Fast, in-process:

* ``collective_axes`` explicit attribution — single-replica / singleton
  groups label ``"replicated"`` instead of matching any axis (the
  ``parse_replica_groups`` None regression), size-1 mesh axes are
  excluded from name matching;
* ``verify_contract`` on a real 1-device-mesh lowering (the degenerate
  mesh satisfies the replicated contract, and a contract demanding real
  data-axis traffic correctly FAILS);
* a deliberately injected extra per-round psum makes ``verify_contract``
  fail while the single-psum control passes;
* the jaxpr auditor's detectors: direct key reuse, a key closed over a
  scan body, fold_in/split-derived keys staying clean, host-sync
  callbacks, f64 leaks, and exact scan-multiplier collective inventories;
* contract JSON round-trip + registry key uniqueness;
* dryrun-style cost analysis on the RANL engines pinned against the
  jaxpr auditor's inventory (XLA may fuse collectives, never invent);
* every lint rule (RPL001-005) on synthetic positive/negative sources,
  and the whole ``src/`` tree linting clean (CI parity).

Slow (subprocess, 8 emulated devices): the ``repro.analysis.audit`` CLI
verifying the committed ``CONTRACTS.json`` for the scan subset, failing
on a tampered registry; ``launch.dryrun.cost_graphs`` per-layer
accounting with a hazard-free bundle jaxpr.
"""

import json
import os
import subprocess
import sys
import textwrap
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import repro
from repro.launch.mesh import make_mesh
from repro.analysis import (
    CollectiveBudget,
    CommContract,
    audit_fn,
    audit_jaxpr,
    contract_key,
    engine_contract,
    verify_contract,
)
from repro.analysis.contracts import (
    JaxprContract,
    contract_from_json,
    contract_to_json,
)
from repro.analysis.lint import lint_paths
from repro.core import make_quadratic
from repro.launch.hlo_analysis import collect_collectives, collective_axes

KEY = jax.random.PRNGKey(0)
REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _problem(dim=32, workers=4, regions=4):
    return make_quadratic(KEY, num_workers=workers, dim=dim, kappa=10.0,
                          coupling=0.0, num_regions=regions)


# --------------------------------------------------------------------------
# axis attribution (the parse_replica_groups None regression)
# --------------------------------------------------------------------------

def test_collective_axes_explicit_replicated():
    # single-replica modules carry no groups: that is "replicated", NOT
    # "matches any axis" (the old behavior this regression pins)
    assert collective_axes(None, (1,), ("data",)) == ("replicated",)
    # all-singleton groups move no data either
    assert collective_axes(((0,), (1,)), (2,), ("data",)) == ("replicated",)
    assert collective_axes(((0, 1),), (2,), ("data",)) == ("data",)
    # a size-1 mesh axis never claims a collective
    assert collective_axes(((0, 1),), (2, 1), ("data", "model")) == ("data",)


def test_collective_axes_three_axis_mesh():
    """Single-axis attribution on the 2x2x2 ("pod","data","model") mesh
    (row-major ids: pod stride 4, data stride 2, model stride 1)."""
    from repro.launch.hlo_analysis import mesh_axis_groups
    sizes, names = (2, 2, 2), ("pod", "data", "model")
    pod_groups = mesh_axis_groups(sizes, 0)
    assert set(map(frozenset, pod_groups)) == {
        frozenset({0, 4}), frozenset({1, 5}),
        frozenset({2, 6}), frozenset({3, 7})}
    assert collective_axes(pod_groups, sizes, names) == ("pod",)
    data_groups = mesh_axis_groups(sizes, 1)
    assert collective_axes(data_groups, sizes, names) == ("data",)
    model_groups = mesh_axis_groups(sizes, 2)
    assert collective_axes(model_groups, sizes, names) == ("model",)


def test_collective_axes_joint_multi_axis_reduction():
    """A JOINT reduction over several axes at once (one collective whose
    groups span e.g. pod x data — the hierarchical engines' init psums)
    attributes to the axis combination instead of the old empty tuple."""
    from repro.launch.hlo_analysis import mesh_axis_groups
    sizes, names = (2, 2, 2), ("pod", "data", "model")
    pd = mesh_axis_groups(sizes, (0, 1))
    assert set(map(frozenset, pd)) == {frozenset({0, 2, 4, 6}),
                                       frozenset({1, 3, 5, 7})}
    assert collective_axes(pd, sizes, names) == ("pod", "data")
    dm = mesh_axis_groups(sizes, (1, 2))
    assert collective_axes(dm, sizes, names) == ("data", "model")
    # the full-mesh reduction is the all-axes combination
    full = mesh_axis_groups(sizes, (0, 1, 2))
    assert full == ((0, 1, 2, 3, 4, 5, 6, 7),)
    assert collective_axes(full, sizes, names) == ("pod", "data", "model")
    # groups matching no axis or combination still return ()
    assert collective_axes(((0, 3), (1, 2), (4, 7), (5, 6)),
                           sizes, names) == ()
    # size-1 axes are excluded from combinations too: on (2, 1, 2) a
    # pod x model joint reduction is just those two real axes
    sizes2 = (2, 1, 2)
    pm = mesh_axis_groups(sizes2, (0, 2))
    assert collective_axes(pm, sizes2, names) == ("pod", "model")


def test_single_replica_mesh_contract_regression():
    prob = _problem()
    opts = repro.RanlOptions(num_rounds=3, num_regions=4)
    mesh = make_mesh((1,), ("data",))
    low = repro.lower(prob, KEY, engine="sharded", mesh=mesh, options=opts)
    comm, mem = engine_contract("sharded", opts, dim=32, num_workers=4,
                                mesh_shape=(1,), mesh_axes=("data",))
    # the derived contract knows the 1-device axis moves no data
    assert comm.budgets[0].axis == "replicated"
    rep = verify_contract(low, comm, mem)
    assert rep.ok, rep.violations
    # ...and a contract demanding real data-axis traffic must NOT be
    # satisfied by the single-replica module
    wrong = replace(comm, budgets=(replace(comm.budgets[0], axis="data"),))
    rep2 = verify_contract(low, wrong)
    assert not rep2.ok
    assert any("found 0" in v for v in rep2.violations), rep2.violations


# --------------------------------------------------------------------------
# verify_contract: the injected-extra-psum failure case
# --------------------------------------------------------------------------

def _toy_loop(n_psums: int):
    mesh = make_mesh((1,), ("data",))

    def body(c, _):
        g = jax.lax.psum(c, "data")
        if n_psums == 2:
            g = g + jax.lax.psum(c * 2.0, "data")
        return c - 0.01 * g, None

    @partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P())
    def step(x):
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    return jax.jit(step).lower(jnp.ones((128,)))


def _toy_contract():
    return CommContract(
        mesh_axes=("data",), mesh_shape=(1,), rounds=3,
        budgets=(CollectiveBudget(axis="replicated", count=1,
                                  min_bytes=512, max_bytes=768,
                                  dtypes=("f32",), multipliers=(3,)),))


def test_verify_contract_fails_on_injected_extra_psum():
    # control: one param-sized psum per round satisfies the contract
    ok_rep = verify_contract(_toy_loop(1), _toy_contract())
    assert ok_rep.ok, ok_rep.violations
    assert len(ok_rep.facts["budgets"][0]["matched"]) == 1
    # the injected second psum violates it (extra budget match and/or an
    # unbudgeted in-loop payload above the small ceiling)
    bad_rep = verify_contract(_toy_loop(2), _toy_contract())
    assert not bad_rep.ok
    assert bad_rep.violations


# --------------------------------------------------------------------------
# jaxpr auditor detectors
# --------------------------------------------------------------------------

def test_jaxpr_audit_direct_key_reuse():
    rep = audit_fn(lambda k: jax.random.normal(k) + jax.random.uniform(k),
                   KEY)
    assert rep.key_reuse and not rep.ok


def test_jaxpr_audit_derived_keys_clean():
    def f(k):
        a = jax.random.normal(jax.random.fold_in(k, 1))
        k2, k3 = jax.random.split(k)
        return a + jax.random.normal(k2) + jax.random.uniform(k3)

    rep = audit_fn(f, KEY)
    assert not rep.key_reuse and rep.ok


def test_jaxpr_audit_key_closed_over_scan_body():
    def bad(k):
        def body(c, _):
            return c + jax.random.normal(k), None
        return jax.lax.scan(body, 0.0, None, length=4)[0]

    assert audit_fn(bad, KEY).key_reuse

    def good(k):
        def body(c, t):
            return c + jax.random.normal(jax.random.fold_in(k, t)), None
        return jax.lax.scan(body, 0.0, jnp.arange(4))[0]

    assert not audit_fn(good, KEY).key_reuse


def test_jaxpr_audit_host_sync():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    assert audit_fn(f, jnp.ones(3)).host_syncs


def test_jaxpr_audit_f64_leak():
    jax.config.update("jax_enable_x64", True)
    try:
        rep = audit_fn(lambda x: x * 2.0, jnp.ones(3, jnp.float64))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert rep.f64_leaks and not rep.ok


def test_jaxpr_audit_scan_multiplier_inventory():
    def f(x):
        return jax.lax.scan(lambda c, _: (c + jax.lax.psum(c, "i"), None),
                            x, None, length=5)[0]

    jaxpr = jax.make_jaxpr(f, axis_env=[("i", 4)])(jnp.ones(3))
    rep = audit_jaxpr(jaxpr)
    assert rep.signature() == {"psum|i|float32[3]|x5": 1}
    assert rep.reduce_count(in_loop=True) == 1
    assert rep.reduce_count(in_loop=False) == 0


def test_engine_traces_are_hazard_free():
    prob = _problem()
    opts = repro.RanlOptions(num_rounds=2, num_regions=4)
    for engine, key in (("scan", KEY), ("reference", KEY),
                        ("batch", jax.random.split(KEY, 2))):
        rep = audit_jaxpr(repro.trace(prob, key, engine=engine,
                                      options=opts))
        assert rep.ok, (engine, rep.key_reuse, rep.f64_leaks,
                        rep.host_syncs)
        # the single-device engines promise ZERO collectives
        assert rep.signature() == {}, (engine, rep.signature())


# --------------------------------------------------------------------------
# contracts: JSON round-trip, registry keys
# --------------------------------------------------------------------------

def test_contract_json_roundtrip():
    opts = repro.RanlOptions(num_rounds=3, ns_iters=8)
    comm, mem = engine_contract("sharded2d", opts, dim=64, num_workers=8,
                                mesh_shape=(2, 2),
                                mesh_axes=("data", "model"))
    jc = JaxprContract(collectives=(("psum|data|float32[32]|x3", 1),))
    entry = json.loads(json.dumps(contract_to_json(comm, mem, jc)))
    comm2, mem2, jc2 = contract_from_json(entry)
    assert comm2 == comm and mem2 == mem and jc2 == jc


def test_contract_keys_unique_across_matrix():
    opts = repro.RanlOptions(num_rounds=3)
    combos = [opts, opts.merged(compression="int8"),
              opts.merged(quorum=0.75), opts.merged(overlap=True),
              opts.merged(hessian_rank=4),
              opts.merged(compression="int8", quorum=0.75, overlap=True)]
    keys = {contract_key(e, o) for e in ("scan", "sharded") for o in combos}
    assert len(keys) == 2 * len(combos)


# --------------------------------------------------------------------------
# dryrun-style cost analysis pinned against the jaxpr inventory
# --------------------------------------------------------------------------

def test_cost_analysis_pinned_to_jaxpr_inventory():
    prob = _problem()
    opts = repro.RanlOptions(num_rounds=3, num_regions=4)
    # scan engine: zero collectives in the jaxpr, and the compiled
    # sharded program's in-loop all-reduce count can never EXCEED the
    # jaxpr's reduce-site count (XLA fuses, it does not invent)
    jscan = audit_jaxpr(repro.trace(prob, KEY, engine="scan",
                                    options=opts))
    assert jscan.signature() == {} and jscan.ok
    mesh = make_mesh((1,), ("data",))
    jsh = audit_jaxpr(repro.trace(prob, KEY, engine="sharded",
                                  options=opts, mesh=mesh))
    n_jaxpr = jsh.reduce_count(in_loop=True)
    assert n_jaxpr >= 1
    compiled = repro.lower(prob, KEY, engine="sharded", options=opts,
                           mesh=mesh).compile()
    ca = compiled.cost_analysis() or {}
    assert float(ca.get("flops", 0.0)) > 0.0
    recs = collect_collectives(compiled.as_text(),
                               default_trip=opts.num_rounds)
    n_hlo = sum(1 for r in recs
                if r.multiplier > 1 and r.kind == "all-reduce")
    assert 1 <= n_hlo <= n_jaxpr, (n_hlo, n_jaxpr)


# --------------------------------------------------------------------------
# lint rules on synthetic sources
# --------------------------------------------------------------------------

def _lint(tmp_path, src, name="mod.py"):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return lint_paths([str(p)])


def test_lint_host_sync_in_scan_body(tmp_path):
    bad = _lint(tmp_path, """
        import jax

        def body(c, x):
            return c, float(c)

        def run(x):
            return jax.lax.scan(body, x, None)
        """)
    assert [v.rule for v in bad] == ["RPL001"]
    good = _lint(tmp_path, """
        import jax

        def body(c, x):
            return c, c * 2

        def run(x):
            v = float(x.shape[0])      # outside the scan body: fine
            return jax.lax.scan(body, x, None), v
        """, name="ok.py")
    assert good == []


def test_lint_nonfrozen_static(tmp_path):
    bad = _lint(tmp_path, """
        import dataclasses
        import jax

        @dataclasses.dataclass
        class Cfg:
            a: int = 1

        def f(x, cfg: Cfg):
            return x

        g = jax.jit(f, static_argnames=("cfg",))
        """)
    assert [v.rule for v in bad] == ["RPL002"]
    good = _lint(tmp_path, """
        import dataclasses
        import jax

        @dataclasses.dataclass(frozen=True)
        class Cfg:
            a: int = 1

        def f(x, cfg: Cfg):
            return x

        g = jax.jit(f, static_argnames=("cfg",))
        """, name="ok.py")
    assert good == []


def test_lint_eigh_confinement(tmp_path):
    bad = _lint(tmp_path, """
        import jax.numpy as jnp

        def decompose(a):
            return jnp.linalg.eigh(a)
        """)
    assert [v.rule for v in bad] == ["RPL003"]
    # core/hessian.py is the one allowed home (the sym_eigh chokepoint)
    allowed = _lint(tmp_path, """
        import jax.numpy as jnp

        def sym_eigh(a):
            return jnp.linalg.eigh(a)
        """, name=os.path.join("core", "hessian.py"))
    assert allowed == []


def test_lint_undeclared_mesh_axis(tmp_path):
    bad = _lint(tmp_path, """
        from jax.sharding import PartitionSpec as P

        SPEC = P("bogus")

        def run(x, axis_name="bogus"):
            return x
        """)
    assert sorted(v.rule for v in bad) == ["RPL004", "RPL004"]
    good = _lint(tmp_path, """
        from jax.sharding import PartitionSpec as P

        SPEC = P("data", "model")

        def run(x, axis_name="data"):
            return x
        """, name="ok.py")
    assert good == []


def test_lint_bare_print(tmp_path):
    bad = _lint(tmp_path, """
        def report(x):
            print("loss", x)
        """)
    assert [v.rule for v in bad] == ["RPL005"]
    # launch/ CLIs may print...
    cli = _lint(tmp_path, """
        def main():
            print("hello")
        """, name=os.path.join("launch", "train.py"))
    assert cli == []
    # ...and so may the report renderer's own module
    rep = _lint(tmp_path, """
        def emit(msg):
            print(msg)
        """, name=os.path.join("obs", "report.py"))
    assert rep == []
    # attribute calls (jax.debug.print) are not bare prints
    dbg = _lint(tmp_path, """
        import jax

        def body(c, x):
            jax.debug.print("c={c}", c=c)
            return c, c
        """, name="dbg.py")
    assert dbg == []


def test_lint_repo_src_clean():
    assert lint_paths([os.path.join(REPO_ROOT, "src")]) == []


# --------------------------------------------------------------------------
# slow: the audit CLI + dryrun cost graphs (subprocess, 8 devices)
# --------------------------------------------------------------------------

def _run(cmd, cwd=None, env_extra=None, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env.update(env_extra or {})
    return subprocess.run(cmd, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.slow
def test_audit_cli_verifies_committed_contracts_and_fails_on_drift(
        tmp_path):
    # the committed registry verifies (scan subset: trace-only, fast)
    out = _run([sys.executable, "-m", "repro.analysis.audit",
                "--engine", "scan"], cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    assert "verified against" in out.stdout, out.stdout

    # a tampered registry is contract drift -> exit 1
    with open(os.path.join(REPO_ROOT, "CONTRACTS.json")) as f:
        registry = json.load(f)
    key = "scan|comp=none|quorum=off|overlap=off|rank=none"
    bad = json.loads(json.dumps(registry))
    bad[key]["jaxpr"]["collectives"] = {"psum|data|f32[64]|x3": 1}
    bad_path = tmp_path / "CONTRACTS.json"
    bad_path.write_text(json.dumps(bad))
    out = _run([sys.executable, "-m", "repro.analysis.audit",
                "--engine", "scan", "--registry", str(bad_path)],
               cwd=REPO_ROOT)
    assert out.returncode == 1, out.stdout + out.stderr[-2000:]
    assert "drift" in out.stdout, out.stdout


@pytest.mark.slow
def test_dryrun_cost_graphs_and_bundle_jaxpr():
    """``launch.dryrun.cost_graphs`` per-layer differenced accounting on
    a tiny LLM config, plus the bundle jaxpr auditing hazard-free."""
    code = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import dataclasses, json
import jax
from repro.launch.mesh import make_mesh
from repro.configs import get_config, smoke_variant, INPUT_SHAPES
from repro.launch.dryrun import cost_graphs
from repro.launch.steps import make_bundle
from repro.models.sharding import use_mesh
from repro.analysis import audit_jaxpr

mesh = make_mesh((2, 4), ('data', 'model'))
cfg = dataclasses.replace(smoke_variant(get_config('hymba-1.5b')),
                          num_layers=4)
shape = dataclasses.replace(INPUT_SHAPES['train_4k'],
                            seq_len=128, global_batch=8)
res = cost_graphs(cfg, shape, mesh)
d = res['derived']
with use_mesh(mesh):
    bundle = make_bundle(cfg, shape, mesh, scan_layers=True)
    jaxpr = jax.make_jaxpr(bundle.fn)(*bundle.abstract_args)
rep = audit_jaxpr(jaxpr)
print(json.dumps({
    'fpl_pos': d['flops_per_layer'] > 0,
    'bpl_pos': d['bytes_per_layer'] > 0,
    'total_consistent': d['flops_total'] >= d['flops_per_layer'] * 3,
    'hazard_free': rep.ok,
    'aval_pos': rep.max_aval_bytes > 0,
}))
"""
    out = _run([sys.executable, "-c", code])
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"fpl_pos": True, "bpl_pos": True,
                   "total_consistent": True, "hazard_free": True,
                   "aval_pos": True}, res
