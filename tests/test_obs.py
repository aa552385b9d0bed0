"""repro.obs: span nesting is well-formed, exports load as Chrome trace
JSON, pool-worker spans merge onto the parent timeline with their own
pids, and observability is behaviour-neutral — certificates and lemma
stats are byte-identical with tracing on or off and across worker
counts."""
import json
import multiprocessing
import os
import time

import pytest

from repro import obs
from repro.api import Suite, verify
from repro.launch.verify import main as verify_main
from repro.modelcheck import check_model
from repro.obs import trace as obs_trace
from repro.obs.inspect import lemma_totals, obligation_rows, render, report
from repro.obs.metrics import MetricsRegistry
from repro.obs.metrics import render as render_metrics
from repro.runtime import RuntimeTask, SupervisedPool
from repro.servecheck import check_serve


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """A test that fails mid-span must not leave its tracer installed."""
    yield
    obs_trace.install(None)


def _nap(t):
    time.sleep(t)
    return t


def _rendezvous_nap(started, n, hold):
    """Check in with our pid, wait until ``n`` distinct worker pids have,
    then hold the worker busy — forces every pool worker to run a task
    regardless of boot-order races, so the distinct-pid assertion below
    is deterministic."""
    started[os.getpid()] = True
    deadline = time.monotonic() + 30.0
    while len(started) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(hold)
    return os.getpid()


def _spans(events):
    return [e for e in events if e.get("ph") == "X"]


# ---------------------------------------------------------------------------
# spans: nesting, export formats
# ---------------------------------------------------------------------------

def test_span_nesting_well_formed():
    tracer = obs_trace.start("t")
    with obs.span("outer", cat="engine", tag=1):
        with obs.span("inner_a"):
            time.sleep(0.001)
        with obs.span("inner_b"):
            time.sleep(0.001)
    obs_trace.stop()
    spans = {e["name"]: e for e in _spans(tracer.events)}
    outer, a, b = spans["outer"], spans["inner_a"], spans["inner_b"]
    assert outer["args"]["depth"] == 0 and outer["args"]["tag"] == 1
    assert a["args"]["depth"] == b["args"]["depth"] == 1
    assert outer["pid"] == a["pid"] == b["pid"] == tracer.pid
    # same-thread intervals: children inside the parent, siblings disjoint
    for inner in (a, b):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert a["ts"] + a["dur"] <= b["ts"]


def test_module_level_api_is_noop_when_off(tmp_path):
    assert obs_trace.current() is None
    with obs.span("nothing"):            # must not raise or record
        obs.event("nothing.event")
        obs.counter("nothing.counter", n=1)
        obs.complete("nothing.span", 1.0, 2.0)
    assert obs_trace.current() is None


def test_chrome_trace_loads_and_has_engine_spans(tmp_path):
    tracer = obs_trace.start("main")
    rep = verify("tp_layer")
    obs_trace.stop()
    assert rep.ok

    path = tmp_path / "trace.json"
    tracer.write_chrome(str(path))
    obj = json.loads(path.read_text())
    assert obj["displayTimeUnit"] == "ms"
    evs = obj["traceEvents"]
    assert evs and evs[0]["ph"] == "M"   # process_name metadata leads
    for e in evs:
        assert {"name", "ph", "ts", "pid"} <= set(e)
    names = {e["name"] for e in evs}
    assert {"capture", "infer", "saturate", "extract",
            "saturate.batch"} <= names
    assert any(n.startswith("op:") for n in names)

    # both export formats round-trip through the inspection loader
    jl = tmp_path / "trace.jsonl"
    tracer.write_jsonl(str(jl))
    assert len(obs_trace.load_events(str(path))) == len(evs)
    assert len(obs_trace.load_events(str(jl))) == \
        len([e for e in evs if e["ph"] != "M"])


# ---------------------------------------------------------------------------
# pool: worker-side spans merge, queue/run split
# ---------------------------------------------------------------------------

def test_worker_spans_merge_with_distinct_pids():
    # spawn, like test_runtime's pool tests: the suite runs jax (pallas
    # interpret) in-process earlier, and fork-starting warm workers after
    # that wedges them in the initializer's first jax op
    tracer = obs_trace.start("main")
    with multiprocessing.get_context("spawn").Manager() as mgr:
        started = mgr.dict()
        tasks = [RuntimeTask(key=f"t{i}", fn=_rendezvous_nap,
                             args=(started, 2, 0.2), budget_s=120.0)
                 for i in range(2)]
        with SupervisedPool(2, mp_method="spawn") as pool:
            outcomes = pool.execute(tasks)
    obs_trace.stop()
    assert all(o.ok for o in outcomes.values())

    task_spans = [e for e in _spans(tracer.events) if e["name"] == "task"]
    assert len(task_spans) == 2
    pids = {e["pid"] for e in task_spans}
    assert len(pids) == 2 and tracer.pid not in pids

    # the supervisor reconstructs every task's run interval (and its
    # queue wait, when it waited) on the parent timeline
    runs = [e for e in tracer.events if e.get("name") == "run"]
    assert {(e.get("args") or {}).get("key")
            for e in runs} == {"t0", "t1"}
    for o in outcomes.values():
        ti = o.timing_info()
        assert set(ti) == {"queue_s", "run_s"}
        assert ti["run_s"] >= 0.2 and ti["queue_s"] >= 0.0


# ---------------------------------------------------------------------------
# behaviour-neutrality: tracing must not change what the engine computes
# ---------------------------------------------------------------------------

def test_certificate_byte_identical_tracing_on_off():
    off = verify("tp_layer")
    tracer = obs_trace.start("main")
    on = verify("tp_layer")
    obs_trace.stop()
    assert tracer.events                 # tracing actually recorded spans
    assert off.ok and on.ok
    assert json.dumps(off.r_o, sort_keys=True) == \
        json.dumps(on.r_o, sort_keys=True)
    for k in ("lemmas", "lemma_fires", "gs_ops", "gd_ops", "egraph_nodes"):
        assert off.stats[k] == on.stats[k], k


CHECKER_SPANS = {"decompose", "capture", "expand", "infer", "seam",
                 "stitch"}


@pytest.mark.parametrize("check", [
    lambda: check_model("gpt", "dp2", workers=0),
    lambda: check_model("gpt", "dp2xtp2", bug="wrong_spec", bug_layer=1,
                        workers=0),
    lambda: check_serve("tp_decode", workers=0),
], ids=["model", "model_refuted", "serve"])
def test_checker_spans_behaviour_neutral(check):
    """The whole-model and serving checks name their capture phases as
    spans; with the tracer on or off the verdict is the same, and each
    obligation's ``phase_s`` holds ``capture`` and ``expand`` either way,
    in a refuted report as in a certified one."""
    off = check()
    tracer = obs_trace.start("main")
    on = check()
    obs_trace.stop()
    assert off.stable_summary() == on.stable_summary()
    for key, rep in off.reports.items():
        assert rep.get("r_o") == on.reports[key].get("r_o")
        for r in (rep, on.reports[key]):
            phases = r["stats"]["phase_s"]
            assert phases["capture"] > 0 and phases["expand"] > 0
    names = {e["name"] for e in _spans(tracer.events)}
    assert CHECKER_SPANS <= names
    graphs = {e["args"]["graph"] for e in _spans(tracer.events)
              if e["name"] == "capture"}
    assert graphs == {"gs", "gd"}
    # the reports' phase sums carry the two phases, inside the wall time
    timing = on.timing()
    captured = timing["phase_s_sum"]["capture"] + \
        timing["phase_s_sum"]["expand"]
    assert 0 < captured <= timing["wall_s"] - timing["infer_s_sum"]


def test_lemma_stats_deterministic_across_worker_counts():
    with Suite(cases=["tp_layer"], degrees=(2,)) as s:
        seq = s.run(workers=0)
        # spawn: fork-starting warm workers wedges after in-process pallas
        par = s.run(workers=2, timeout_s=120.0, mp_method="spawn")
    a = seq.reports[0].stats["lemmas"]
    b = par.reports[0].stats["lemmas"]
    assert a and a == b
    for row in a.values():
        assert set(row) == {"calls", "hits", "fires"}
        assert row["hits"] <= row["calls"]
    # the suite aggregates the runtime's queue/run split alongside
    assert par.summary()["runtime"]["tasks"] == 1
    assert "runtime" not in json.dumps(par.stable_summary())


# ---------------------------------------------------------------------------
# inspection: renderer + metrics registry
# ---------------------------------------------------------------------------

def test_inspect_render_names_top_lemma(tmp_path, capsys):
    tracer = obs_trace.Tracer("main")
    tracer.event("saturate.batch", cat="engine",
                 fires={"concat_merge": 5, "slice_cover": 1},
                 ms={"concat_merge": 2.0, "slice_cover": 1.0})
    tracer.complete("queue", 10.0, 10.5, key="ob1")
    tracer.complete("run", 10.5, 11.0, key="ob1", status="ok")

    totals = lemma_totals(tracer.events)
    assert totals["concat_merge"] == {"fires": 5, "ms": 2.0}
    rows = obligation_rows(tracer.events)
    assert rows[0]["key"] == "ob1"
    assert rows[0]["queue_ms"] == pytest.approx(500.0)
    assert rows[0]["run_ms"] == pytest.approx(500.0)

    out = render(tracer.events)
    assert "ob1" in out and "queue" in out
    assert out.endswith("top lemma: concat_merge")

    # CLI wrapper: 0 on a readable trace, 1 on an empty one
    p = tmp_path / "t.json"
    tracer.write_chrome(str(p))
    assert report(str(p)) == 0
    assert "top lemma: concat_merge" in capsys.readouterr().out
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report(str(empty)) == 1


def test_metrics_registry_and_render():
    reg = MetricsRegistry()
    reg.counter("cache.hits").inc()
    reg.counter("cache.hits").inc(2)
    h = reg.histogram("pool.queue_s")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"] == {"cache.hits": 3}
    hs = snap["histograms"]["pool.queue_s"]
    assert hs["count"] == 4 and hs["sum"] == 10.0
    assert hs["min"] == 1.0 and hs["max"] == 4.0
    text = render_metrics(reg)
    assert text.startswith("-- metrics --")
    assert "cache.hits" in text and "pool.queue_s" in text
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "histograms": {}}
    assert "(no metrics recorded)" in render_metrics(reg)


def test_histogram_reservoir_is_deterministic():
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg in (a, b):
        h = reg.histogram("x")
        for i in range(3 * h.SAMPLE + 7):    # wraps the ring twice
            h.observe(i % 97)
    assert a.snapshot() == b.snapshot()


# ---------------------------------------------------------------------------
# CLI: --trace / --metrics
# ---------------------------------------------------------------------------

def _case_envelope(capsys, argv):
    try:
        verify_main(argv)
    except SystemExit as e:
        assert e.code in (None, 0)
    return json.loads(capsys.readouterr().out)


def _stable_report(env):
    rep = json.loads(json.dumps(env["report"]))
    rep.pop("wall_s", None)
    rep.pop("runtime", None)
    stats = rep.get("stats") or {}
    stats.pop("time_s", None)
    stats.pop("phase_s", None)
    return json.dumps(rep, sort_keys=True)


def test_cli_trace_does_not_change_envelope_or_certificate(tmp_path, capsys):
    plain = _case_envelope(capsys, ["--case", "tp_layer", "--json"])
    traced = _case_envelope(
        capsys, ["--case", "tp_layer", "--json",
                 "--trace", str(tmp_path / "t.json")])
    # the pinned four-key schema-v2 envelope with or without --trace
    assert set(plain) == set(traced) == \
        {"schema_version", "kind", "timing", "report"}
    assert _stable_report(plain) == _stable_report(traced)


def test_cli_trace_and_metrics_flags(tmp_path, capsys):
    trace_path = tmp_path / "trace.json"
    verify_main(["--case", "tp_layer", "--json",
                 "--trace", str(trace_path), "--metrics"])
    cap = capsys.readouterr()
    env = json.loads(cap.out)
    # "metrics" joins the envelope only under the flag
    assert set(env) == {"schema_version", "kind", "timing", "report",
                        "metrics"}
    assert env["metrics"]["counters"].get("engine.runs", 0) >= 1
    assert "-- metrics --" in cap.err and "[obs] wrote" in cap.err

    assert trace_path.exists()
    assert (tmp_path / "trace.json.jsonl").exists()
    events = obs_trace.load_events(str(trace_path))
    assert any(e.get("name") == "infer" for e in events)
    assert "top lemma:" in render(events)
    assert obs_trace.current() is None   # the CLI uninstalled its tracer
