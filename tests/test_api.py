"""repro.api surface: registry semantics, verify() round-trips over every
registered strategy, Report serialization, and Suite determinism across
worker counts and engine-optimization settings."""
import json
import multiprocessing
import os
import time

import pytest

from repro.api import (BugSpec, DuplicateStrategyError, Report, StrategySpec,
                       Suite, axis_degrees, build_spec, bug_host,
                       degree_token, get_strategy, list_bugs,
                       list_strategies, normalize_degree, parse_degree,
                       register_strategy, verify)
from repro.api.spec import task_id
from repro.api.registry import _REGISTRY
from repro.api.spec import EXPECTED_VERDICT
from repro.launch.verify import CASES, run_case

ALL_CASES = list_strategies()
ALL_BUGS = sorted(list_bugs())

HAVE_FORK = "fork" in multiprocessing.get_all_start_methods()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_covers_paper_matrix():
    assert set(ALL_CASES) == {"tp_layer", "sp_rope", "sp_pad", "ep_moe",
                              "aux_loss", "sp_moe", "grad_accum", "ln_grad",
                              "fsdp_mlp", "pp_stage", "tp_dp_2d"}
    assert set(ALL_BUGS) == {"rope_offset", "aux_scale", "pad_slice",
                             "sharded_expert", "grad_accum",
                             "ln_no_allreduce", "stale_shard",
                             "rs_wrong_axis", "drop_microbatch",
                             "psum_wrong_axis"}
    # the 2D-mesh case declares per-axis tuple degrees, incl. the 16-rank
    # (4, 4) mesh the n-ary add normal form made tractable
    assert get_strategy("tp_dp_2d").degrees == ((2, 2), (2, 4), (4, 2),
                                                (4, 4))


def test_duplicate_registration_raises():
    with pytest.raises(DuplicateStrategyError):
        @register_strategy("tp_layer")
        def tp_again(degree=2, bug=None):  # pragma: no cover — never built
            raise AssertionError


def test_duplicate_bug_name_raises():
    """A shadowed bug name would re-host the bug past the wrong-host
    guard, silently verifying the clean graph."""
    with pytest.raises(DuplicateStrategyError, match="rope_offset"):
        @register_strategy("_thief", bugs=[BugSpec("rope_offset")])
        def _thief(degree=2, bug=None):  # pragma: no cover — never built
            raise AssertionError
    assert "_thief" not in list_strategies()


def test_register_rejects_bad_expectation():
    with pytest.raises(ValueError):
        register_strategy("nope", expected="refinement_error")
    with pytest.raises(ValueError):
        BugSpec("b", expected="certificate")


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        get_strategy("no_such_case")
    with pytest.raises(KeyError):
        build_spec("no_such_case")
    with pytest.raises(KeyError):
        bug_host("no_such_bug")


@pytest.mark.parametrize("api_call", [
    lambda: verify("tp_layer", bug="rope_offset"),
    lambda: build_spec("tp_layer", bug="rope_offset"),
    lambda: run_case("tp_layer", bug="rope_offset", quiet=True),
])
def test_wrong_host_bug_guard(api_call):
    """Running a bug under the wrong case would silently verify the clean
    graph — the guard must fire through every entry point."""
    with pytest.raises(ValueError, match="belongs to case"):
        api_call()


def test_legacy_cases_view_mirrors_registry():
    assert set(CASES) == set(ALL_CASES)
    seq_fn, dist_fn, axes, specs, avals, names = CASES["tp_layer"](degree=2)
    assert callable(seq_fn) and callable(dist_fn)
    assert axes == {"tp": 2} and names == ["x", "w1", "w2"]


# ---------------------------------------------------------------------------
# StrategySpec
# ---------------------------------------------------------------------------

def test_spec_is_frozen_and_stamped():
    spec = build_spec("sp_rope", degree=4, bug="rope_offset")
    assert isinstance(spec, StrategySpec)
    assert (spec.name, spec.degree, spec.bug) == ("sp_rope", 4, "rope_offset")
    assert spec.expected == "refinement_error"
    assert spec.task_id() == "sp_rope@deg4+rope_offset"
    with pytest.raises(Exception):      # dataclasses.FrozenInstanceError
        spec.degree = 2


def test_spec_iterates_as_legacy_6tuple():
    spec = build_spec("ep_moe")
    tup = tuple(spec)
    assert len(tup) == 6
    assert tup[2] == {"ep": 2} and tup[5] == ["x", "w"]
    assert spec.as_tuple()[0] is spec.seq_fn


# ---------------------------------------------------------------------------
# multi-axis degree plumbing
# ---------------------------------------------------------------------------

def test_degree_normalization_and_tokens():
    assert normalize_degree(4) == 4
    assert normalize_degree([2, 4]) == (2, 4)
    assert normalize_degree((4,)) == 4          # 1-tuple collapses to int
    assert degree_token(4) == "4"
    assert degree_token([4, 2]) == "4x2"
    assert task_id("tp_dp_2d", (2, 4)) == "tp_dp_2d@deg2x4"
    assert task_id("tp_dp_2d", (2, 4), "psum_wrong_axis") == \
        "tp_dp_2d@deg2x4+psum_wrong_axis"


def test_parse_degree_cli_values():
    """`--degrees` accepts ints and per-axis `NxM` values (argparse type)."""
    assert parse_degree("4") == 4
    assert parse_degree("2x4") == (2, 4)
    assert parse_degree("2x2x2") == (2, 2, 2)
    for bad in ("x", "2x", "a", "2xa", "", "0", "-2", "2x0", "2x-1"):
        with pytest.raises(ValueError, match="bad degree"):
            parse_degree(bad)


def test_tuple_degree_rejected_for_single_axis_cases():
    """A per-axis tuple on a single-axis case must be a clear error, not an
    opaque TypeError inside the builder — and the Suite fails fast on it
    instead of aborting mid-matrix."""
    with pytest.raises(ValueError, match="single-axis"):
        build_spec("tp_layer", degree=(2, 4))
    with pytest.raises(ValueError, match="single-axis"):
        verify("sp_moe", degree=(2, 2))
    with pytest.raises(ValueError, match="single-axis"):
        Suite(degrees=[(2, 4)])
    with pytest.raises(ValueError, match="2.*-axis degrees"):
        build_spec("tp_dp_2d", degree=(2, 2, 2))   # wrong arity


def test_axis_degrees_broadcast_and_mismatch():
    assert axis_degrees(4, 2) == (4, 4)         # scalar broadcasts
    assert axis_degrees((4, 2), 2) == (4, 2)
    with pytest.raises(ValueError, match="2 entries for a 3-axis"):
        axis_degrees((4, 2), 3)


def test_multiaxis_spec_stamping_and_legacy_tuple():
    """A 2D-mesh spec carries its per-axis degree (normalized to a tuple)
    and still unpacks as the legacy 6-tuple."""
    spec = build_spec("tp_dp_2d", degree=[4, 2])      # list normalizes
    assert spec.degree == (4, 2)
    assert spec.task_id() == "tp_dp_2d@deg4x2"
    seq_fn, dist_fn, axes, specs, avals, names = spec
    assert callable(seq_fn) and callable(dist_fn)
    assert axes == {"dp": 4, "tp": 2}
    assert names == ["x", "w1", "w2"]
    # scalar degree broadcasts to both mesh axes
    assert build_spec("tp_dp_2d", degree=2).mesh_axes == {"dp": 2, "tp": 2}


def test_multiaxis_report_json_roundtrip():
    report = verify("tp_dp_2d", degree=(2, 2))
    assert report.ok and report.degree == (2, 2)
    back = Report.from_json(json.loads(json.dumps(report.to_json())))
    assert back.degree == (2, 2)                 # list -> tuple on the way in
    assert back.task_id() == report.task_id() == "tp_dp_2d@deg2x2"


def test_suite_sweeps_tuple_degrees_from_registry():
    tasks = Suite(cases=["tp_dp_2d"], include_bugs=True).tasks()
    ids = [t.task_id() for t in tasks]
    assert ids == ["tp_dp_2d@deg2x2", "tp_dp_2d@deg2x2+psum_wrong_axis",
                   "tp_dp_2d@deg2x4", "tp_dp_2d@deg2x4+psum_wrong_axis",
                   "tp_dp_2d@deg4x2", "tp_dp_2d@deg4x2+psum_wrong_axis",
                   "tp_dp_2d@deg4x4", "tp_dp_2d@deg4x4+psum_wrong_axis"]


# ---------------------------------------------------------------------------
# the FSDP / pipeline / 2D-mesh families (bug detection at degree 2 and 4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("degree", [2, 4])
def test_fsdp_bugs_detected(degree):
    clean = verify("fsdp_mlp", degree=degree)
    assert clean.ok and clean.verdict == "certificate"
    stale = verify("fsdp_mlp", degree=degree, bug="stale_shard")
    assert stale.ok and stale.verdict == "refinement_error"
    assert stale.localization["op_name"] == "matmul"
    # wrong scatter axis: clean certificate, but R_o assembles the grad
    # shards along dim 1 instead of dim 0 (paper bug 5 detection mode)
    wrong = verify("fsdp_mlp", degree=degree, bug="rs_wrong_axis")
    assert wrong.ok and wrong.verdict == "certificate"
    assert wrong.r_o != clean.r_o
    (grad_out,) = [k for k, v in wrong.r_o.items() if "dim=1" in v]
    assert "dim=0" in clean.r_o[grad_out]


@pytest.mark.parametrize("degree", [2, 4])
def test_pp_dropped_microbatch_detected(degree):
    clean = verify("pp_stage", degree=degree)
    assert clean.ok and clean.verdict == "certificate"
    # the whole pipeline's output lives on the last stage's rank
    assert list(clean.r_o.values())[0].endswith(f"@pp{degree - 1}")
    bug = verify("pp_stage", degree=degree, bug="drop_microbatch")
    assert bug.ok and bug.verdict == "refinement_error"


def test_tp_dp_2d_wrong_axis_detected():
    bug = verify("tp_dp_2d", degree=(2, 2), bug="psum_wrong_axis")
    assert bug.ok and bug.verdict == "refinement_error"


@pytest.mark.slow
@pytest.mark.parametrize("degree", [(2, 4), (4, 2), (4, 4)])
def test_tp_dp_2d_degree4_axes(degree):
    """Degree 4 on either (or both) mesh axes certifies and catches the
    wrong-axis psum — (4, 4) was a scale gap until the n-ary add normal
    form replaced assoc/comm saturation."""
    clean = verify("tp_dp_2d", degree=degree)
    assert clean.ok and clean.verdict == "certificate"
    bug = verify("tp_dp_2d", degree=degree, bug="psum_wrong_axis")
    assert bug.ok and bug.verdict == "refinement_error"


# ---------------------------------------------------------------------------
# verify() round-trips the whole registry (no hand-copied lists)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ALL_CASES)
def test_verify_roundtrip_every_strategy(case):
    entry = get_strategy(case)
    report = verify(case, degree=2)
    assert report.ok, (report.verdict, report.expected, report.error)
    assert report.verdict == EXPECTED_VERDICT[entry.expected]
    if report.verdict == "certificate":
        assert report.r_o and all(isinstance(v, str)
                                  for v in report.r_o.values())
        assert report.stats["egraph_nodes"] > 0
        assert report.certificate is not None
    else:
        assert report.localization is not None
        assert report.localization["op_index"] >= 0


@pytest.mark.parametrize("bug", ALL_BUGS)
def test_verify_every_bug_through_registry(bug):
    host, bspec = list_bugs()[bug]
    report = verify(host, degree=2, bug=bug)
    assert report.ok, (bug, report.verdict, report.expected)
    if bspec.expected == "refinement_error":
        assert report.verdict == "refinement_error"
        assert report.localization["op_name"]
    else:                                # paper bug 5: clean-but-unexpected
        assert report.verdict == "certificate"
        clean = verify(host, degree=2)
        assert report.r_o != clean.r_o   # the unexpected relation


def test_verify_rejects_selectors_with_prebuilt_spec():
    spec = build_spec("sp_moe", degree=4)
    assert verify(spec).ok                    # spec alone is fine
    with pytest.raises(ValueError, match="already built"):
        verify(spec, degree=8)
    with pytest.raises(ValueError, match="already built"):
        verify(spec, bug="rope_offset")


def test_suite_rejects_bad_bug_filters():
    with pytest.raises(KeyError, match="unknown bug"):
        Suite(bugs=["rope_offzet"])
    with pytest.raises(ValueError, match="never run"):
        Suite(cases=["tp_layer"], bugs=["rope_offset"])


def test_report_json_roundtrip():
    report = verify("tp_layer")
    blob = json.dumps(report.to_json(), sort_keys=True)
    back = Report.from_json(json.loads(blob))
    assert back.to_json() == report.to_json()
    assert back.certificate is None      # live object never serialized


def test_engine_opts_restored_after_verify():
    from repro.core.profile import CONFIG
    before = CONFIG.as_dict()
    verify("ln_grad", engine_opts={"optimizations": False})
    assert CONFIG.as_dict() == before
    with pytest.raises(ValueError, match="unknown engine_opts"):
        verify("ln_grad", engine_opts={"max_nodez": 5})


# ---------------------------------------------------------------------------
# Suite
# ---------------------------------------------------------------------------

def test_suite_matrix_shape():
    suite = Suite(include_bugs=True)
    tasks = suite.tasks()
    by_id = [t.task_id() for t in tasks]
    assert len(by_id) == len(set(by_id))
    # bugs ride along only under their host case, at the host's degrees
    for t in tasks:
        if t.bug is not None:
            assert bug_host(t.bug) == t.case
        assert t.degree in get_strategy(t.case).degrees
    # grad_accum caps at degree 4 (batch divisibility)
    assert "grad_accum@deg8" not in by_id
    assert "ln_grad@deg2+ln_no_allreduce" in by_id


def test_suite_sequential_clean_matrix():
    result = Suite(degrees=(2,)).run(workers=0)
    assert len(result) == len(ALL_CASES) and result.ok
    md = result.to_markdown()
    assert "tp_layer@deg2" in md
    blob = json.dumps(result.to_json())
    assert "certificate" in blob


def test_suite_matches_checked_in_golden():
    """The CI gate in scripts/ci.sh `suite`, as a unit test: every
    registered strategy must still produce its golden verdict + R_o."""
    golden_path = os.path.join(os.path.dirname(__file__), "golden",
                               "suite_degree2.json")
    with open(golden_path) as f:
        golden = json.load(f)
    got = Suite(degrees=(2,)).run(workers=0).stable_summary()
    assert got == golden


@pytest.mark.slow
@pytest.mark.skipif(not HAVE_FORK, reason="needs fork start method")
def test_suite_deterministic_across_workers_and_opt():
    """Certificates must be byte-identical for any worker count and any
    GRAPHGUARD_OPT setting (extends the engine-ablation invariant to the
    parallel runner)."""
    cases = ["tp_layer", "sp_moe", "ln_grad"]
    summaries = []
    for opts in (True, False):
        for workers in (0, 2):
            with Suite(cases=cases, degrees=(2,),
                       engine_opts={"optimizations": opts}) as s:
                summaries.append(
                    json.dumps(s.run(workers=workers,
                                     mp_method="fork").stable_summary(),
                               sort_keys=True))
    assert len(set(summaries)) == 1, "results varied with workers/opt"


@pytest.mark.slow
@pytest.mark.skipif(not HAVE_FORK, reason="needs fork start method")
def test_suite_per_task_timeout():
    """A wedged task is reported as verdict=timeout without sinking the
    rest of the matrix, and the poisoned pool is discarded."""
    @register_strategy("_sleepy", degrees=(2,))
    def _sleepy(degree=2, bug=None):
        time.sleep(30)               # pragma: no cover — killed by timeout
        raise AssertionError
    try:
        with Suite(cases=["_sleepy", "ln_grad"], degrees=(2,)) as s:
            # fork: the child must inherit the test-registered strategy
            result = s.run(workers=2, timeout_s=2.0, mp_method="fork")
        by_case = {r.case: r for r in result}
        assert by_case["_sleepy"].verdict == "timeout"
        assert not by_case["_sleepy"].ok
        assert by_case["ln_grad"].verdict == "certificate"
        assert not result.ok
    finally:
        _REGISTRY.pop("_sleepy", None)
