"""Observability layer (``repro.obs``) tests.

The rails the tentpole promises:

* journal schema: header-first / summary-last / strictly-increasing
  rounds, JSONL round-trip through ``read_journal``/``validate_journal``,
  and every negative the validator must catch;
* **bit-exactness**: ``repro.run(..., journal=...)`` on every engine
  (compression, quorum and hierarchy options included) produces the
  identical trajectory as the journal-off run — observability reads
  host-side results only;
* the **contract-drift alarm**: fires on an injected byte-budget
  mismatch, stays silent at the modeled worst-case (full-mask) wire
  bytes of every combination in the committed contract matrix
  (``analysis.audit._configs`` — the 37 CONTRACTS.json entries);
* span tracing: nesting, zero-cost inactivity, Chrome-trace export,
  and the profiler sink: ``repro.run``'s ``ranl.*`` spans land in a
  ``jax.profiler`` trace with clean names, nested by phase, with JAX's
  compile spans under them;
* the report CLI: render (text/Markdown/time-to-target), diff,
  validate, and the committed ``examples/sample_journal.jsonl``;
* train CLI integration: ``--journal``/``--trace`` leave a valid
  journal with ``ranl.train.{lower,compile,execute}`` spans, and
  ``--dump-hlo --journal`` surfaces ``module_report``/``cost_analysis``
  byte totals into the journal header;
* the overhead pin: committed ``BENCH_engine.json`` obs rows within
  1.05x and the regression gate's enforcement of it.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.launch.mesh import make_mesh
from repro.core import PolicyConfig, make_quadratic
from repro.obs import (Journal, check_byte_drift, hlo_header, make_header,
                       read_journal, span, tracing, validate_journal,
                       write_run_journal)
from repro.obs.report import diff, render, render_diff, render_md
from repro.obs.report import main as report_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KEY = jax.random.PRNGKey(0)


def _problem(num_workers=4, dim=16):
    return make_quadratic(KEY, num_workers=num_workers, dim=dim,
                          kappa=50.0, coupling=0.0, num_regions=4)


def _opts(**kw):
    base = dict(num_rounds=5, num_regions=4,
                policy=PolicyConfig(keep_prob=0.5, tau_star=1,
                                    heterogeneous=False))
    base.update(kw)
    return repro.RanlOptions(**base)


# --------------------------------------------------------------------------
# journal schema + round-trip
# --------------------------------------------------------------------------

def test_journal_roundtrip_and_schema(tmp_path):
    path = tmp_path / "run.jsonl"
    res = repro.run(_problem(), KEY, options=_opts(), journal=str(path))
    records = read_journal(path)
    assert validate_journal(records) == []
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "header" and kinds[-1] == "summary"
    assert kinds.count("round") == 5
    head = records[0]
    assert head["engine"] == "scan"
    assert head["options"]["num_rounds"] == 5
    assert head["contract_key"].startswith("scan|")
    assert head["problem"] == {"dim": 16, "num_workers": 4}
    assert set(head["byte_budget"]) == {"comm_per_round", "pod_per_round"}
    rounds = [r for r in records if r["kind"] == "round"]
    assert [r["t"] for r in rounds] == [1, 2, 3, 4, 5]
    for r in rounds:
        assert {"coverage", "comm_floats", "comm_bytes", "loss",
                "dist_sq", "round_time", "sim_s"} <= set(r)
    # cumulative sim clock is monotone and matches the summary total
    sims = [r["sim_s"] for r in rounds]
    assert sims == sorted(sims)
    assert records[-1]["sim_total"] == pytest.approx(sims[-1])
    assert records[-1]["final_loss"] == pytest.approx(rounds[-1]["loss"])


def test_journal_in_memory_and_context_manager(tmp_path):
    with Journal(tmp_path / "j.jsonl") as j:
        repro.run(_problem(), KEY, options=_opts(num_rounds=2), journal=j)
    assert validate_journal(j) == []
    assert validate_journal(read_journal(tmp_path / "j.jsonl")) == []
    mem = Journal()                                   # no file at all
    repro.run(_problem(), KEY, options=_opts(num_rounds=2), journal=mem)
    assert mem.path is None and validate_journal(mem) == []


def test_journal_record_every_thins_losses_not_rounds(tmp_path):
    res = repro.run(_problem(), KEY, options=_opts(num_rounds=7,
                                                   record_every=3),
                    journal=str(tmp_path / "thin.jsonl"))
    records = read_journal(tmp_path / "thin.jsonl")
    assert validate_journal(records) == []
    rounds = [r for r in records if r["kind"] == "round"]
    assert [r["t"] for r in rounds] == [1, 2, 3, 4, 5, 6, 7]
    with_loss = [r["t"] for r in rounds if "loss" in r]
    assert with_loss == [3, 6, 7]                 # kept iterates only
    for r in rounds:                              # traces never thinned
        assert "coverage" in r and "comm_bytes" in r


def test_validate_journal_negatives():
    head = {"kind": "header", "schema": 1, "engine": "scan",
            "options": {}, "version": "0"}
    rnd = {"kind": "round", "t": 1, "loss": 1.0}
    assert validate_journal([]) != []
    assert any("header" in p for p in validate_journal([rnd]))
    assert any("schema" in p for p in
               validate_journal([{**head, "schema": 99}]))
    assert any("duplicate" in p for p in validate_journal([head, head]))
    assert any("unknown kind" in p for p in
               validate_journal([head, {"kind": "bogus"}]))
    assert any("not increasing" in p for p in
               validate_journal([head, rnd, {"kind": "round", "t": 1}]))
    assert any("must be an int" in p for p in
               validate_journal([head, {"kind": "round", "t": "one"}]))
    assert any("must be numeric" in p for p in
               validate_journal([head, {"kind": "round", "t": 1,
                                        "loss": "nan-ish"}]))
    assert any("summary must be the last" in p for p in
               validate_journal([head, {"kind": "summary"}, rnd]))
    ok = [head, rnd, {"kind": "round", "t": 2}, {"kind": "summary"}]
    assert validate_journal(ok) == []


# --------------------------------------------------------------------------
# bit-exactness: journal on == journal off, every engine
# --------------------------------------------------------------------------

def _assert_bit_exact(engine, opts, key, *, mesh=None):
    kw = dict(engine=engine, options=opts, mesh=mesh)
    ref = repro.run(_problem(), key, **kw)
    j = Journal()
    res = repro.run(_problem(), key, journal=j, **kw)
    np.testing.assert_array_equal(np.asarray(ref.xs), np.asarray(res.xs))
    assert validate_journal(j) == []
    return j


@pytest.mark.parametrize("opts_kw", [
    {},                                            # plain
    {"compression": "int8"},                       # compressed uplink
    {"quorum": 0.75},                              # semi-sync commit
    {"hierarchy": "pods=2,period=2", "num_rounds": 4},   # pod-of-pods
    {"hierarchy": "pods=2,period=2,compression=int8", "num_rounds": 4},
])
def test_bit_exact_scan(opts_kw):
    j = _assert_bit_exact("scan", _opts(**opts_kw), KEY)
    assert not [r for r in j.records if r["kind"] == "drift"]


def test_bit_exact_reference():
    _assert_bit_exact("reference", _opts(), KEY)


def test_bit_exact_batch_seeds_header():
    keys = jax.random.split(KEY, 3)
    ref = repro.run(_problem(), keys, engine="batch", options=_opts())
    j = Journal()
    res = repro.run(_problem(), keys, engine="batch", options=_opts(),
                    journal=j)
    np.testing.assert_array_equal(np.asarray(ref.xs), np.asarray(res.xs))
    assert validate_journal(j) == []
    assert j.records[0]["seeds"] == 3               # batch axis surfaced
    stale = [r["max_stale"] for r in j.records if r["kind"] == "round"]
    assert all(isinstance(s, int) for s in stale)   # max-reduced, not mean


def test_bit_exact_sharded_one_device():
    mesh = make_mesh((1,), ("data",))
    _assert_bit_exact("sharded", _opts(), KEY, mesh=mesh)


def test_bit_exact_sharded2d_one_device():
    mesh = make_mesh((1, 1), ("data", "model"))
    _assert_bit_exact("sharded2d", _opts(), KEY, mesh=mesh)


# --------------------------------------------------------------------------
# the contract-drift alarm
# --------------------------------------------------------------------------

def test_drift_alarm_fires_on_injected_mismatch():
    budget = {"comm_per_round": 256.0, "pod_per_round": 128.0}
    rounds = [{"kind": "round", "t": 1, "comm_bytes": 256.0,
               "pod_bytes": 128.0},
              {"kind": "round", "t": 2, "comm_bytes": 300.0,
               "pod_bytes": 130.0}]
    out = check_byte_drift(rounds, budget)
    assert [(d["t"], d["metric"]) for d in out] == [
        (2, "comm_bytes"), (2, "pod_bytes")]
    for d in out:
        assert d["kind"] == "drift" and d["observed"] > d["budget"]
        assert "exceeds the contract byte budget" in d["message"]
    # at-the-limit rounds are NOT drift (exact worst case is in-contract)
    assert check_byte_drift(rounds[:1], budget) == []


def test_drift_alarm_in_journal_on_injected_budget(tmp_path):
    j = Journal()
    res = repro.run(_problem(), KEY, options=_opts())
    # sabotage the derivation: shrink the budget under the observed wire
    from repro.analysis import contracts
    real = contracts.round_byte_budget

    def tiny(opts, *, dim, num_workers):
        return {"comm_per_round": 1.0, "pod_per_round": 1.0}
    contracts.round_byte_budget = tiny
    try:
        write_run_journal(j, res, engine="scan", options=_opts(),
                          problem=_problem())
    finally:
        contracts.round_byte_budget = real
    drift = [r for r in j.records if r["kind"] == "drift"]
    assert len(drift) == 5                       # every round over budget
    assert validate_journal(j) == []             # drift records are valid


def test_drift_alarm_silent_across_committed_contract_matrix():
    """The modeled worst case (full participation) of every combination
    in the committed contract matrix stays within its derived byte
    budget — the alarm can only fire on genuine drift."""
    from repro.analysis.audit import DIM, NUM_REGIONS, NUM_WORKERS, _configs
    from repro.analysis.contracts import round_byte_budget
    from repro.core.compression import uplink_bytes
    from repro.core.ranl import _pod_wire_bytes

    sizes_q = jnp.full((NUM_REGIONS,), DIM // NUM_REGIONS,
                       dtype=jnp.int32)
    full = jnp.ones((NUM_WORKERS, NUM_REGIONS), dtype=bool)
    n_checked = 0
    for engine, opts, _mesh in _configs():
        budget = round_byte_budget(opts, dim=DIM, num_workers=NUM_WORKERS)
        comp = opts.compression_spec()
        comm = float(uplink_bytes(comp, full, sizes_q).sum())
        hspec = opts.hierarchy_spec()
        from repro.core.compression import parse_compression
        pod_comp = parse_compression(hspec.compression) if hspec else comp
        pod = float(_pod_wire_bytes(pod_comp, DIM))
        rec = {"kind": "round", "t": 1, "comm_bytes": comm,
               "pod_bytes": pod}
        assert check_byte_drift([rec], budget) == [], (engine, opts)
        n_checked += 1
    # the matrix is the committed registry: every entry exercised
    with open(os.path.join(REPO_ROOT, "CONTRACTS.json")) as f:
        assert n_checked == len(json.load(f))


# --------------------------------------------------------------------------
# span tracing
# --------------------------------------------------------------------------

def test_span_noop_without_tracer():
    from repro.obs.trace import current_tracer
    assert current_tracer() is None
    with span("anything") as t:                  # must not record or fail
        assert t is None


def test_tracer_spans_nesting_and_chrome(tmp_path):
    with tracing() as tr:
        with span("outer", engine="scan"):
            with span("inner"):
                pass
    names = [s.name for s in tr.spans]
    assert names == ["inner", "outer"]           # close order
    tot = tr.totals()
    assert tot["outer"] >= tot["inner"] >= 0.0
    recs = tr.span_records()
    assert all(r["kind"] == "span" for r in recs)
    assert recs[1]["meta"] == {"engine": "scan"}
    p = tmp_path / "trace.json"
    tr.write_chrome(p)
    ct = json.loads(p.read_text())
    assert [e["name"] for e in ct["traceEvents"]] == names
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in ct["traceEvents"])


INIT_PHASES = ["ranl.init.grad", "ranl.init.hessian", "ranl.init.project",
               "ranl.init.factor"]
RUN_SPANS = INIT_PHASES + ["ranl.init", "ranl.rounds", "ranl.result",
                           "ranl.run"]                   # close order
COMPILE_SPANS = ("backend_compile_and_load", "backend_compile")


def test_run_records_execute_span_into_journal():
    with tracing():
        j = Journal()
        repro.run(_problem(), KEY, options=_opts(num_rounds=2), journal=j)
    spans = [r for r in j.records if r["kind"] == "span"]
    assert [s["name"] for s in spans] == RUN_SPANS
    assert spans[-1]["meta"] == {"engine": "scan"}
    assert validate_journal(j) == []


def test_lower_records_span():
    mesh = make_mesh((1,), ("data",))
    with tracing() as tr:
        repro.lower(_problem(), KEY, engine="sharded", options=_opts(),
                    mesh=mesh)
    assert [s.name for s in tr.spans] == INIT_PHASES + ["ranl.init",
                                                        "ranl.lower"]


def _host_events(fn, log_dir):
    """Run ``fn`` under ``jax.profiler.trace`` and read back the host
    events of the ``.xplane.pb``: (name, start_ns, end_ns, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        fn()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("engine", ["scan", "sharded"])
def test_run_spans_nest_in_profiler_trace(engine, tmp_path):
    # a dim no other test compiles, so the call is cold and compiles
    prob = _problem(dim=12)
    mesh = make_mesh((1,), ("data",)) if engine == "sharded" else None
    evs = _host_events(
        lambda: jax.block_until_ready(repro.run(
            prob, KEY, engine=engine, options=_opts(num_rounds=2),
            mesh=mesh).xs), tmp_path)
    ranl = sorted((e for e in evs if e[0].startswith("ranl.")),
                  key=lambda e: e[1])
    assert [e[0] for e in ranl] == ["ranl.run", "ranl.init"] + INIT_PHASES \
        + ["ranl.rounds", "ranl.result"]
    assert not any(e[0].startswith("bench.") for e in evs)
    run_, init = ranl[0], ranl[1]
    assert run_[3] == {"engine": engine}
    assert all(_inside(e, run_) for e in ranl[1:])
    assert all(_inside(e, init) for e in ranl[2:6])
    # the phases follow one another: init, then rounds, then result
    assert all(a[2] <= b[1] for a, b in zip(ranl[2:6], ranl[3:6]))
    assert init[2] <= ranl[6][1] and ranl[6][2] <= ranl[7][1]
    assert any(e[0] in COMPILE_SPANS and _inside(e, run_) for e in evs)


def test_span_meta_leaves_event_name_clean(tmp_path):
    def body():
        with span("ranl.probe", engine="scan", step=3) as t:
            assert t is None                     # no tracer pushed
        with tracing() as tr:
            with span("ranl.probe", step=4):
                pass
        assert tr.spans[0].meta == (("step", 4),)
    probes = [e for e in _host_events(body, tmp_path)
              if "ranl.probe" in e[0]]
    assert [e[0] for e in probes] == ["ranl.probe", "ranl.probe"]
    assert probes[0][3] == {"engine": "scan", "step": 3}
    assert probes[1][3] == {"step": 4}


# --------------------------------------------------------------------------
# report CLI
# --------------------------------------------------------------------------

def _two_journals(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    repro.run(_problem(), KEY, options=_opts(), journal=str(a))
    repro.run(_problem(), KEY, options=_opts(compression="int8"),
              journal=str(b))
    return str(a), str(b)


def test_report_render_text_md_target(tmp_path):
    a, _ = _two_journals(tmp_path)
    records = read_journal(a)
    txt = render(records, target=1e30)           # trivially reached
    assert "run journal summary" in txt
    assert "uplink bytes/round" in txt and "round 1" in txt
    assert "staleness histogram" in txt
    md = render_md(records)
    assert md.startswith("# Run journal summary")
    assert "\\|" in md                           # contract key escaped
    unreached = render(records, target=-1.0)
    assert "not reached" in unreached


def test_report_diff(tmp_path):
    a, b = _two_journals(tmp_path)
    d = diff(read_journal(a), read_journal(b))
    assert d["engine"] == {"a": "scan", "b": "scan"}
    ratio = d["comm_bytes_total"]["ratio"]
    assert 0 < ratio < 1                         # int8 moves fewer bytes
    out = render_diff(read_journal(a), read_journal(b))
    assert "journal diff" in out and "comm_bytes_total" in out


def test_report_cli_main(tmp_path, capsys):
    a, b = _two_journals(tmp_path)
    assert report_main([a]) == 0
    assert report_main([a, "--md", "--target", "1e30"]) == 0
    assert report_main([a, "--validate"]) == 0
    assert report_main(["--diff", a, b]) == 0
    assert report_main(["--diff", a, b, "--md"]) == 0
    out = capsys.readouterr().out
    assert "run journal summary" in out and "Journal diff" in out
    # invalid journal: nonzero + problems on stderr
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "round", "t": 1}\n')
    assert report_main([str(bad), "--validate"]) == 1
    assert "header" in capsys.readouterr().err


def test_committed_sample_journal_renders():
    path = os.path.join(REPO_ROOT, "examples", "sample_journal.jsonl")
    records = read_journal(path)
    assert validate_journal(records) == []
    assert not [r for r in records if r["kind"] == "drift"]
    txt = render(records, target=1e-4)
    assert "pod bytes/round" in txt              # hierarchical sample
    assert report_main([path, "--md"]) == 0


# --------------------------------------------------------------------------
# hlo header: module_report + dry-run cost_analysis surfaced
# --------------------------------------------------------------------------

def test_hlo_header_byte_totals(tmp_path):
    from repro.launch.hlo_analysis import cost_raw_summary, module_report

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    compiled = f.lower(jnp.ones((8, 8), jnp.float32)).compile()
    cost = cost_raw_summary(compiled)
    assert cost.get("flops", 0) > 0              # dryrun-style raw cost
    rep = module_report(compiled.as_text())
    hdr = hlo_header(rep, cost)
    assert hdr["max_array_bytes"] >= 8 * 8 * 4
    assert hdr["collective_bytes"] == rep["collectives"]["total_bytes"]
    assert hdr["cost_raw"] == cost
    header = make_header(engine="scan", options={}, hlo=hdr)
    j = Journal(tmp_path / "h.jsonl")
    j.write(header)
    j.write({"kind": "summary"})
    j.close()
    records = read_journal(tmp_path / "h.jsonl")
    assert validate_journal(records) == []
    assert records[0]["hlo"]["cost_raw"]["flops"] == cost["flops"]
    assert isinstance(records[0]["hlo"]["per_collective"], list)


def test_hlo_header_counts_in_loop_collectives():
    mesh = make_mesh((1,), ("data",))
    txt = repro.lower(_problem(), KEY, engine="sharded", options=_opts(),
                      mesh=mesh).compile().as_text()
    from repro.launch.hlo_analysis import module_report
    hdr = hlo_header(module_report(txt))
    assert hdr["in_loop_collective_bytes"] >= 0
    assert hdr["collective_bytes"] >= hdr["in_loop_collective_bytes"] >= 0
    for row in hdr["per_collective"]:
        assert {"kind", "operand_bytes", "multiplier",
                "operand_dtypes"} <= set(row)


# --------------------------------------------------------------------------
# train CLI integration
# --------------------------------------------------------------------------

def test_train_cli_journal_and_trace(tmp_path):
    from repro.launch.train import run
    jpath, tpath = str(tmp_path / "t.jsonl"), str(tmp_path / "t.trace")
    hist = run(["--arch", "phi4-mini-3.8b", "--smoke", "--steps", "3",
                "--batch", "4", "--seq", "32", "--workers", "4",
                "--log-every", "100", "--journal", jpath,
                "--trace", tpath])
    assert len(hist) == 3                        # journal records all steps
    records = read_journal(jpath)
    assert validate_journal(records) == []
    head = records[0]
    assert head["engine"] == "train:ranl" and head["arch"] == "phi4-mini-3.8b"
    rounds = [r for r in records if r["kind"] == "round"]
    assert [r["t"] for r in rounds] == [1, 2, 3]
    assert all("loss" in r and "step_s" in r for r in rounds)
    spans = {r["name"] for r in records if r["kind"] == "span"}
    assert {"ranl.train.lower", "ranl.train.compile",
            "ranl.train.execute"} <= spans
    ct = json.loads(open(tpath).read())
    assert {"ranl.train.lower", "ranl.train.compile"} <= {
        e["name"] for e in ct["traceEvents"]}


def test_train_cli_log_every_thins_history(tmp_path):
    from repro.launch.train import run
    hist = run(["--arch", "phi4-mini-3.8b", "--smoke", "--steps", "5",
                "--batch", "4", "--seq", "32", "--workers", "4",
                "--log-every", "100"])
    # host syncs only on log/last steps: step 0 and the final step
    assert len(hist) == 2
    assert "loss" in hist[0] and "loss" in hist[-1]


@pytest.mark.slow
def test_train_dump_hlo_journal_header(tmp_path):
    from repro.launch.train import run
    jpath = str(tmp_path / "hlo.jsonl")
    rep = run(["--arch", "phi4-mini-3.8b", "--smoke", "--steps", "1",
               "--batch", "4", "--seq", "32", "--workers", "4",
               "--dump-hlo", str(tmp_path / "step.hlo"),
               "--journal", jpath])
    records = read_journal(jpath)
    assert validate_journal(records) == []
    hlo = records[0]["hlo"]
    assert hlo["max_array_bytes"] == rep["max_array_bytes"]
    assert hlo["collective_bytes"] == rep["collectives"]["total_bytes"]
    assert hlo["cost_raw"]["flops"] > 0          # dryrun cost_analysis
    assert len(hlo["per_collective"]) >= len(rep["records"])


# --------------------------------------------------------------------------
# overhead pin
# --------------------------------------------------------------------------

def test_committed_bench_obs_overhead_within_pin():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_regression",
        os.path.join(REPO_ROOT, "benchmarks", "regression.py"))
    regression = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regression)
    with open(os.path.join(REPO_ROOT, "BENCH_engine.json")) as f:
        rows = {r["name"]: r for r in json.load(f)}
    on, off = rows["engine/obs_on"], rows["engine/obs_off"]
    ratio = on["us_per_call"] / off["us_per_call"]
    assert ratio <= regression.OBS_OVERHEAD_LIMIT == 1.05
    assert "overhead=" in on["derived"]
    # the gate trips on a violating fresh row set and passes the real one
    lines = []
    bad = {"engine/obs_off": {"us_per_call": 100.0},
           "engine/obs_on": {"us_per_call": 120.0}}
    assert regression.obs_overhead_gate(bad, lines)
    assert regression.obs_overhead_gate(rows, lines) == []
