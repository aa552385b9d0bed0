"""repro.gradcheck: train-step strategies certify per-parameter, injected
gradient bugs localize to the offending parameter, relations transpose
from the forward specs, and the versioned CLI --json envelope is stable
across all three paths (case / --model / --train)."""
import json

import pytest

from repro.api import check_train_task, list_train_tasks
from repro.gradcheck import (TrainReport, capture_grad, check_train,
                             expected_grad_relation, get_train_strategy,
                             grad_collective, list_train_bugs,
                             list_train_strategies, register_train_strategy)
from repro.launch.verify import main as verify_main

ALL_TRAIN = list_train_strategies()
ALL_TRAIN_BUGS = sorted(list_train_bugs())


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_train_registry_covers_strategies_and_bugs():
    assert set(ALL_TRAIN) == {"dp", "dp_accum", "fsdp", "tp_dp_2d"}
    assert set(ALL_TRAIN_BUGS) == {"accum_no_rescale", "stale_grad_shard",
                                   "grad_psum_wrong_axis"}
    assert list_train_tasks() == tuple(f"train@{s}" for s in ALL_TRAIN)
    # the 16-rank mesh the n-ary add normal form made tractable is swept
    assert (4, 4) in get_train_strategy("tp_dp_2d").degrees


def test_train_registry_guards():
    with pytest.raises(KeyError, match="unknown train strategy"):
        get_train_strategy("no_such")
    with pytest.raises(ValueError, match="belongs to train strategy"):
        get_train_strategy("dp").build(bug="accum_no_rescale")
    with pytest.raises(ValueError, match="not hosted"):
        check_train("dp", bug="stale_grad_shard")
    with pytest.raises(ValueError, match="single-axis"):
        check_train("dp", degree=(2, 2))
    with pytest.raises(ValueError, match="already registered"):
        register_train_strategy("dp")(lambda degree=2, bug=None: {})
    with pytest.raises(KeyError, match="bad train task"):
        check_train_task("dp")                 # missing the train@ prefix


# ---------------------------------------------------------------------------
# backward capture
# ---------------------------------------------------------------------------

def test_capture_grad_backward_graph():
    """capture_grad traces the backward of a loss into a sequential Graph:
    the w2 gradient of sum(tanh(x@w1)@w2) is a transposed-matmul program
    whose single output has w2's shape."""
    from repro.gradcheck.obligations import _AVALS, _NAMES, _loss

    g = capture_grad(_loss, _AVALS, _NAMES, wrt=2)
    assert g.n_ops > 0 and len(g.outputs) == 1
    assert g.shapes[g.outputs[0]] == tuple(_AVALS[2].shape)
    ops = {t.op for _, t in g.defs} | {
        op for _, t in g.defs for op in t.ops_used()}
    assert "matmul" in ops and "transpose" in ops   # the AD transpose


# ---------------------------------------------------------------------------
# relation transposition
# ---------------------------------------------------------------------------

def test_grad_collective_transposition():
    from jax.sharding import PartitionSpec as P
    mesh = {"dp": 2}
    # replicated param, dp-sharded data -> psum over dp
    assert grad_collective(P(), P("dp", None), mesh) == ("psum", ("dp",))
    # dp-sharded param, dp-sharded data -> reduce_scatter (ZeRO)
    assert grad_collective(P("dp", None), P("dp", None), mesh) == \
        ("reduce_scatter", ("dp",))
    # replicated data -> nothing owed
    assert grad_collective(P(), P(), mesh) == ("identity", ())
    # 2D mesh: tp-sharded param, dp-sharded data -> psum over dp only
    assert grad_collective(P(None, "tp"), P("dp", None),
                           {"dp": 2, "tp": 2}) == ("psum", ("dp",))


def test_expected_grad_relation_terms():
    from jax.sharding import PartitionSpec as P
    # replicated parameter: identity at replica coordinate 0
    t = expected_grad_relation("g", (4, 4), "f", P(), {"dp": 2})
    assert str(t) == "g@dp0"
    # sharded parameter: the concat of shards (the transposed forward map)
    t = expected_grad_relation("g", (2, 4), "f", P("dp", None), {"dp": 2})
    assert str(t) == "concat(g@dp0, g@dp1, dim=0)"


# ---------------------------------------------------------------------------
# clean certification + bug localization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ALL_TRAIN)
def test_train_strategy_certifies(strategy):
    report = check_train(strategy)
    assert report.ok and report.verdict == "certificate", \
        (strategy, report.failing_params)
    assert not report.failing_params
    for p in report.params:
        assert p.verdict == "certificate" and p.relation_ok
        assert p.collective.startswith(("psum", "reduce_scatter"))


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ALL_TRAIN)
def test_train_strategy_certifies_at_all_degrees(strategy):
    for degree in get_train_strategy(strategy).degrees:
        report = check_train(strategy, degree=degree)
        assert report.ok, (strategy, degree, report.failing_params)


@pytest.mark.parametrize("bug", ALL_TRAIN_BUGS)
def test_train_bug_localizes_to_parameter(bug):
    host, bspec = list_train_bugs()[bug]
    target = get_train_strategy(host).bug_params[bug]
    report = check_train(host, bug=bug)
    assert report.ok, (bug, report.verdict, report.failing_params)
    assert report.verdict == "refinement_error"
    # sharp localization: exactly the offending parameter fails, the
    # sibling parameter's gradient still certifies
    assert report.failing_params == [target] == [report.bug_param]
    by_param = {p.param: p for p in report.params}
    assert by_param[target].verdict == "refinement_error"
    assert by_param[target].localized_op
    for p in report.params:
        if p.param != target:
            assert p.verdict == "certificate" and p.relation_ok


def test_train_report_json_roundtrip():
    report = check_train("dp")
    blob = json.dumps(report.to_json(), sort_keys=True)
    back = TrainReport.from_json(json.loads(blob))
    assert back.stable_summary() == report.stable_summary()
    assert back.task_id() == report.task_id() == "train@dp@deg2"
    md = report.to_markdown()
    assert "psum(dp)" in md and "certificate" in md


def test_check_train_task_api():
    report = check_train_task("train@fsdp", degree=2)
    assert report.ok and report.verdict == "certificate"
    assert {p.collective for p in report.params} == {"reduce_scatter(dp)"}


# ---------------------------------------------------------------------------
# the versioned --json envelope across all three CLI paths
# ---------------------------------------------------------------------------

def _envelope(capsys, argv):
    try:
        verify_main(argv)
    except SystemExit as e:               # bug paths exit(1) by design
        assert e.code in (None, 0, 1)
    return json.loads(capsys.readouterr().out)


@pytest.mark.parametrize("kind,argv", [
    ("case", ["--case", "tp_layer", "--json"]),
    ("model", ["--model", "gpt", "--plan", "dp2", "--json"]),
    ("train", ["--train", "dp", "--json"]),
])
def test_json_envelope_all_paths(capsys, kind, argv):
    """Every CLI path emits the same versioned envelope: schema_version,
    kind, per-phase timing, report — and the envelope byte-identically
    survives a json.loads -> json.dumps round trip."""
    env = _envelope(capsys, argv)
    assert env["schema_version"] == 2
    assert env["kind"] == kind
    assert set(env) == {"schema_version", "kind", "timing", "report"}
    # timing.phase_s keys are the engine's stable phase names, and a
    # model check's obligations add their capture and SPMD expansion
    phases = env["timing"].get("phase_s") or env["timing"].get("phase_s_sum")
    assert phases is not None
    assert set(phases) <= {"saturate", "rebuild", "frontier", "extract",
                           "capture", "expand"}
    assert {"saturate", "extract"} <= set(phases)
    assert ({"capture", "expand"} <= set(phases)) == (kind == "model")
    blob = json.dumps(env, indent=2, sort_keys=True)
    assert json.dumps(json.loads(blob), indent=2, sort_keys=True) == blob


def _stable_envelope(env):
    """Strip timing-dependent fields, keep every certificate byte."""
    env = json.loads(json.dumps(env))     # deep copy
    env.pop("timing", None)
    rep = env["report"]
    for k in ("wall_s", "workers", "timing", "pool"):
        rep.pop(k, None)
    for nested in (rep.get("reports") or {}).values():
        nested.pop("stats", None)
        nested.pop("wall_s", None)
    rep.pop("stats", None)
    return json.dumps(env, sort_keys=True)


def test_train_envelope_identical_across_worker_counts(capsys):
    """The --train envelope's stable content (verdicts, certificates,
    relations) must be byte-identical for any worker count."""
    a = _envelope(capsys, ["--train", "dp_accum", "--json", "--workers", "1"])
    b = _envelope(capsys, ["--train", "dp_accum", "--json", "--workers", "2"])
    assert a["report"]["workers"] != b["report"]["workers"]
    assert _stable_envelope(a) == _stable_envelope(b)


def test_cli_list_kind_tags(capsys):
    verify_main(["--list"])
    out = capsys.readouterr().out
    assert "[case]" in out and "[model]" in out and "[train]" in out
    assert "train@dp_accum" in out
    assert "accum_no_rescale" in out
