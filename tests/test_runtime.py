"""Tests for repro.runtime — the fault-tolerant execution layer.

Three layers of coverage:

* cache units: journal roundtrip, torn/garbage line recovery, compaction,
  engine-fingerprint rotation, ``resolve_cache`` semantics, commit policy;
* pool units: per-task budgets (no shared-deadline starvation), crash
  quarantine with victim-only attribution, bounded retry recovery,
  in-process degradation, chaos containment;
* scheduler integration: Suite / check_model / check_train under injected
  faults — only the afflicted task errors, everything else stays
  byte-identical, and a warm cache resumes re-proving only what's missing.
"""
import json
import multiprocessing
import os
import time

import pytest

from repro.api import Suite, build_spec
from repro.runtime import (CertificateCache, DEFAULT_CACHE_DIR, PoolUnavailable,
                           RuntimeTask, SupervisedPool, cacheable_report,
                           chaos, execute_inline, obligation_cache_key,
                           resolve_cache, run_tasks, strategy_cache_key)
from repro.runtime.cache import ENV_CACHE_DIR, _line_for
from repro.runtime.pool import default_start_method

HAVE_FORK = "fork" in multiprocessing.get_all_start_methods()
needs_fork = pytest.mark.skipif(not HAVE_FORK,
                                reason="needs fork start method")


@pytest.fixture(autouse=True)
def _no_ambient_chaos(monkeypatch):
    """Chaos/cache env must never leak between tests (or in from the
    invoking shell)."""
    for var in (chaos.ENV_SPEC, chaos.ENV_TARGET, chaos.ENV_SEED,
                ENV_CACHE_DIR):
        monkeypatch.delenv(var, raising=False)


# module-level so pool workers can pickle them ------------------------------

def _report(tag):
    return {"verdict": "certificate", "tag": tag}


def _nondeterministic_report(tag):
    return {"verdict": "error", "tag": tag}


def _sleep_report(tag, seconds):
    time.sleep(seconds)
    return {"verdict": "certificate", "tag": tag}


def _boom(tag):
    raise RuntimeError(f"synthetic failure for {tag}")


def _wedge_forever():
    time.sleep(3600)


def _task(key, fn=_report, args=None, **kw):
    kw.setdefault("budget_s", 30.0)      # bound the worst case: a wedged
    return RuntimeTask(key=key, fn=fn, args=args or (key,), **kw)


def _jax_platforms(tag):
    import jax
    return jax.config.jax_platforms


# these tasks never touch jax, so pool tests skip the jax warm-up
# initializer (warm=False) — forked workers stay pure-python.  Fork is
# asked for by name: once the test process's JAX backend is up, the pool's
# default start method is spawn.
POOL_KW = {"warm": False, "mp_method": "fork"}


# ---------------------------------------------------------------------------
# certificate cache
# ---------------------------------------------------------------------------

class TestCertificateCache:
    def test_roundtrip_and_stats(self, tmp_path):
        c = CertificateCache(tmp_path / "c")
        assert c.get("k1") is None           # miss
        c.put("k1", {"verdict": "certificate", "r_o": {"y": "x"}})
        assert c.get("k1") == {"verdict": "certificate", "r_o": {"y": "x"}}
        assert "k1" in c and len(c) == 1
        s = c.stats()
        assert (s["hits"], s["misses"], s["entries"]) == (1, 1, 1)
        # a fresh handle on the same directory sees the committed entry
        c2 = CertificateCache(tmp_path / "c")
        assert c2.get("k1")["r_o"] == {"y": "x"}
        assert c2.recovered_corrupt == 0

    def test_get_returns_defensive_copy(self, tmp_path):
        c = CertificateCache(tmp_path / "c")
        c.put("k", {"verdict": "certificate", "r_o": {"y": "x"}})
        c.get("k")["r_o"]["y"] = "tampered"
        assert c.get("k")["r_o"] == {"y": "x"}

    def test_torn_tail_line_recovered(self, tmp_path):
        c = CertificateCache(tmp_path / "c")
        for i in range(3):
            c.put(f"k{i}", {"verdict": "certificate", "i": i})
        # simulate the writer dying mid-append: cut the last line in half
        raw = open(c.journal_path, "rb").read()
        torn_at = len(raw) - (len(raw) - raw[:-1].rfind(b"\n") - 1) // 2
        with open(c.journal_path, "wb") as f:
            f.write(raw[:torn_at])
        c2 = CertificateCache(tmp_path / "c")
        assert c2.recovered_corrupt == 1
        assert len(c2) == 2 and "k2" not in c2
        assert c2.get("k0") == {"verdict": "certificate", "i": 0}

    def test_garbage_and_bad_digest_lines_skipped(self, tmp_path):
        c = CertificateCache(tmp_path / "c")
        c.put("good", {"verdict": "certificate"})
        with open(c.journal_path, "ab") as f:
            f.write(b"\x00\xffnot even text\n")
            # right shape, wrong digest (bit rot on the payload)
            line = _line_for("evil", {"verdict": "certificate"})
            f.write(line[:17] + b"X" + line[18:])
        c2 = CertificateCache(tmp_path / "c")
        assert c2.recovered_corrupt == 2
        assert len(c2) == 1 and "evil" not in c2

    def test_compact_drops_corruption(self, tmp_path):
        c = CertificateCache(tmp_path / "c")
        c.put("a", {"verdict": "certificate"})
        c.put("b", {"verdict": "certificate"})
        with open(c.journal_path, "ab") as f:
            f.write(b"garbage line\n")
        c.compact()
        lines = open(c.journal_path, "rb").read().splitlines()
        assert len(lines) == 2               # one clean line per live key
        c2 = CertificateCache(tmp_path / "c")
        assert len(c2) == 2 and c2.recovered_corrupt == 0

    def test_engine_fingerprint_rotation(self, tmp_path):
        d = tmp_path / "c"
        c = CertificateCache(d)
        c.put("k", {"verdict": "certificate"})
        meta = json.load(open(d / "meta.json"))
        meta["engine"] = "0" * len(meta["engine"])
        json.dump(meta, open(d / "meta.json", "w"))
        # a different engine must not reuse these proofs: journal rotates
        # aside instead of being reinterpreted
        c2 = CertificateCache(d)
        assert len(c2) == 0
        assert os.path.exists(str(d / "journal.jsonl") + ".stale")
        # the rewritten meta makes a third open warm again
        c2.put("k", {"verdict": "certificate"})
        assert len(CertificateCache(d)) == 1

    def test_resolve_cache_semantics(self, tmp_path, monkeypatch):
        assert resolve_cache(False) is None
        assert resolve_cache(None) is None           # no env, no cache
        monkeypatch.setenv(ENV_CACHE_DIR, str(tmp_path / "env"))
        assert resolve_cache(None).dir == str(tmp_path / "env")
        assert resolve_cache(False) is None          # False beats the env
        monkeypatch.chdir(tmp_path)
        assert resolve_cache(True).dir == DEFAULT_CACHE_DIR
        c = resolve_cache(tmp_path / "explicit")
        assert isinstance(c, CertificateCache)
        assert resolve_cache(c) is c                 # instance passthrough

    def test_cache_keys_embed_engine_limits(self):
        k = obligation_cache_key("blk-abc123")
        assert k.startswith("ob:blk-abc123:mn")
        assert obligation_cache_key("blk-abc123", {"max_nodes": 7}) \
            == "ob:blk-abc123:mn7"
        s2 = strategy_cache_key(build_spec("tp_layer", degree=2))
        assert s2 != strategy_cache_key(build_spec("sp_rope", degree=2))
        assert s2 != strategy_cache_key(build_spec("tp_layer", degree=2),
                                        {"max_nodes": 7})
        assert s2 == strategy_cache_key(build_spec("tp_layer", degree=2))

    def test_commit_policy_only_deterministic_verdicts(self):
        assert cacheable_report({"verdict": "certificate"})
        assert cacheable_report({"verdict": "refinement_error"})
        assert not cacheable_report({"verdict": "error"})
        assert not cacheable_report({"verdict": "timeout"})
        assert not cacheable_report("certificate")   # not a report dict


# ---------------------------------------------------------------------------
# chaos config
# ---------------------------------------------------------------------------

class TestChaos:
    def test_parse_spec(self):
        cfg = chaos.parse_spec("crash:0.3, hang:0.1", target="tp", seed=7)
        assert cfg.p("crash") == 0.3 and cfg.p("hang") == 0.1
        assert cfg.p("exit") == 0.0
        with pytest.raises(ValueError, match="unknown chaos mode"):
            chaos.parse_spec("explode:1")
        with pytest.raises(ValueError, match="not mode:prob"):
            chaos.parse_spec("crash")
        with pytest.raises(ValueError, match="must be in"):
            chaos.parse_spec("crash:1.5")

    def test_should_is_deterministic_and_targeted(self):
        cfg = chaos.parse_spec("crash:1", target="victim")
        assert chaos.should("crash", "the-victim-task", cfg=cfg)
        assert not chaos.should("crash", "innocent", cfg=cfg)
        assert not chaos.should("hang", "the-victim-task", cfg=cfg)
        half = chaos.parse_spec("crash:0.5", seed=3)
        draws = [chaos.should("crash", "k", a, half) for a in range(64)]
        assert draws == [chaos.should("crash", "k", a, half)
                         for a in range(64)]          # replayable
        assert any(draws) and not all(draws)          # attempt-varying

    def test_maybe_fault_is_noop_outside_workers(self, monkeypatch):
        monkeypatch.setenv(chaos.ENV_SPEC, "crash:1,exit:1,hang:1")
        chaos.maybe_fault("anything")    # would SIGSEGV us in a worker
        assert chaos.load_config().p("crash") == 1.0


# ---------------------------------------------------------------------------
# pool semantics
# ---------------------------------------------------------------------------

class TestPool:
    def test_inline_execution(self):
        out = execute_inline([_task("a"), _task("b")])
        assert out["a"].ok and out["a"].value == _report("a")
        assert out["b"].ok
        assert out["a"].runtime_info() == {}   # happy path stays silent

    def test_inline_task_error_contained(self):
        out = execute_inline([_task("bad", fn=_boom), _task("good")])
        assert out["bad"].status == "error"
        assert "synthetic failure" in out["bad"].error
        assert out["good"].ok                  # neighbour unaffected

    @needs_fork
    def test_pool_matches_inline(self):
        tasks = [_task(f"t{i}") for i in range(4)]
        pooled = run_tasks(tasks, workers=2, **POOL_KW)
        inline = run_tasks(tasks, workers=0)
        for k in inline:
            assert pooled[k].ok and pooled[k].value == inline[k].value
            assert pooled[k].runtime_info() == inline[k].runtime_info() == {}

    def test_duplicate_keys_rejected(self):
        with SupervisedPool(2, warm=False) as pool:
            with pytest.raises(ValueError, match="duplicate task keys"):
                pool.execute([_task("dup"), _task("dup")])

    @needs_fork
    def test_per_task_budget_not_shared(self):
        """Regression for the shared-deadline starvation bug: one slow
        task exhausts only its own budget — queued siblings still get
        their full budget and finish."""
        tasks = [_task("slow", fn=_sleep_report, args=("slow", 30.0),
                       budget_s=1.5)]
        tasks += [_task(f"quick{i}", budget_s=30.0) for i in range(3)]
        out = run_tasks(tasks, workers=2, **POOL_KW)
        assert out["slow"].status == "timeout"
        assert "budget" in out["slow"].error
        assert 1.0 <= out["slow"].wall_s < 10.0    # measured, not assumed
        for i in range(3):
            q = out[f"quick{i}"]
            assert q.ok and q.attempts == 1

    @needs_fork
    def test_crash_blamed_on_victim_only(self, monkeypatch):
        monkeypatch.setenv(chaos.ENV_SPEC, "crash:1")
        monkeypatch.setenv(chaos.ENV_TARGET, "victim")
        out = run_tasks([_task("victim"), _task("bystander-a"),
                         _task("bystander-b")], workers=2, **POOL_KW)
        v = out["victim"]
        assert v.status == "error" and v.attempts == 3
        assert "all 3 attempts" in v.error and "SIGSEGV" in v.error
        for k in ("bystander-a", "bystander-b"):
            assert out[k].ok and out[k].value == _report(k)

    @needs_fork
    def test_hard_exit_cause_reported(self, monkeypatch):
        monkeypatch.setenv(chaos.ENV_SPEC, "exit:1")
        monkeypatch.setenv(chaos.ENV_TARGET, "victim")
        out = run_tasks([_task("victim"), _task("ok")], workers=2,
                        **POOL_KW)
        assert out["victim"].status == "error"
        assert "exit code 3" in out["victim"].error
        assert out["ok"].ok

    @needs_fork
    def test_transient_crash_recovers_with_retry(self, monkeypatch):
        """A fault on the first attempt only: the quarantine retry gets a
        clean result and reports attempts > 1."""
        def cfg(seed):
            return chaos.parse_spec("crash:0.5", target="flaky", seed=seed)
        seed = next(s for s in range(1000)
                    if chaos.should("crash", "flaky", 1, cfg(s))
                    and not chaos.should("crash", "flaky", 2, cfg(s)))
        monkeypatch.setenv(chaos.ENV_SPEC, "crash:0.5")
        monkeypatch.setenv(chaos.ENV_TARGET, "flaky")
        monkeypatch.setenv(chaos.ENV_SEED, str(seed))
        out = run_tasks([_task("flaky")], workers=2, **POOL_KW)
        assert out["flaky"].ok and out["flaky"].value == _report("flaky")
        assert out["flaky"].attempts == 2
        assert out["flaky"].runtime_info() == {"attempts": 2}

    @needs_fork
    def test_wedged_worker_startup_times_out(self):
        """Liveness regression: a worker that wedges before its first
        heartbeat (e.g. on a fork-inherited lock) must burn the task's
        budget from executor pick-up, not hang execute() forever."""
        with SupervisedPool(2, warm=False, mp_method="fork") as pool:
            pool._initializer = _wedge_forever
            out = pool.execute([_task("stuck", budget_s=2.0)])
        assert out["stuck"].status == "timeout"
        assert "wedged during startup" in out["stuck"].error
        assert out["stuck"].wall_s >= 1.5

    def test_spawn_worker_pinned_to_cpu(self, monkeypatch):
        """Workers never open an accelerator the parent may hold: with no
        JAX_PLATFORMS in the environment they start from, the pool's
        initializer still pins their JAX to the CPU backend."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        out = run_tasks([_task("w", fn=_jax_platforms)], workers=2,
                        mp_method="spawn", warm=False)
        assert out["w"].ok and out["w"].value == "cpu"

    def test_default_start_method_spawns_once_backend_is_up(self):
        import jax.numpy as jnp
        (jnp.zeros((1,)) + 1).block_until_ready()
        assert default_start_method() == "spawn"
        assert SupervisedPool(2, warm=False).mp_method == "spawn"

    def test_degrades_inline_when_pool_unavailable(self, monkeypatch):
        pool = SupervisedPool(2, warm=False)

        def no_pool(size):
            raise PoolUnavailable("no child processes on this host")
        monkeypatch.setattr(pool, "_make_executor", no_pool)
        try:
            out = pool.execute([_task("a"), _task("b")])
        finally:
            pool.shutdown()
        for k in ("a", "b"):
            assert out[k].ok and out[k].value == _report(k)
            assert "no child processes" in out[k].degraded_reason
            assert "degraded_reason" in out[k].runtime_info()

    def test_worker_chaos_never_fires_in_process(self, monkeypatch):
        # inline (workers <= 1) must survive crash:1 — a worker-side fault
        # fired in-process would take down the caller, the exact failure
        # the runtime exists to contain
        monkeypatch.setenv(chaos.ENV_SPEC, "crash:1,exit:1,hang:1")
        out = run_tasks([_task("a")], workers=0)
        assert out["a"].ok

    @needs_fork
    def test_pool_cache_hit_skips_execution(self, tmp_path):
        cache = CertificateCache(tmp_path / "c")
        sentinel = {"verdict": "certificate", "tag": "from-cache"}
        cache.put("ck-hit", sentinel)
        out = run_tasks([_task("hit", cache_key="ck-hit"),
                         _task("miss", cache_key="ck-miss")],
                        workers=2, cache=cache, **POOL_KW)
        assert out["hit"].value == sentinel
        assert out["hit"].cache == "hit" and out["hit"].attempts == 0
        assert out["miss"].cache == "miss"
        assert cache.get("ck-miss") == _report("miss")   # committed

    def test_nondeterministic_verdicts_never_cached(self, tmp_path):
        cache = CertificateCache(tmp_path / "c")
        out = execute_inline([_task("e", fn=_nondeterministic_report,
                                    cache_key="ck-e")], cache=cache)
        assert out["e"].ok and out["e"].cache == "miss"
        assert "ck-e" not in cache           # error verdicts must re-prove


# ---------------------------------------------------------------------------
# scheduler integration: faults stay contained, certificates stay identical
# ---------------------------------------------------------------------------

SUITE_CASES = ("tp_layer", "sp_rope")


def _suite_summaries(result):
    return {r.task_id(): json.dumps(r.stable_summary(), sort_keys=True)
            for r in result}


class TestSchedulerFaults:
    @pytest.mark.slow
    def test_suite_crash_survivors_identical(self, monkeypatch):
        """The crash-afflicted task fails alone with the crash attributed,
        and every survivor is byte-identical to a fault-free run.  Spawn
        workers: a fork pool created this deep into a jax-threaded pytest
        session can wedge on a fork-inherited lock (that containment path
        is covered by test_wedged_worker_startup_times_out)."""
        baseline = Suite(cases=SUITE_CASES, degrees=(2,)).run(workers=0)
        monkeypatch.setenv(chaos.ENV_SPEC, "crash:1")
        monkeypatch.setenv(chaos.ENV_TARGET, "tp_layer@deg2")
        with Suite(cases=SUITE_CASES, degrees=(2,)) as s:
            hit = s.run(workers=2, timeout_s=60.0, mp_method="spawn")
        by = {r.task_id(): r for r in hit}
        victim = by["tp_layer@deg2"]
        assert victim.verdict == "error" and not victim.ok
        assert "SIGSEGV" in victim.error
        assert victim.runtime["attempts"] == 3
        base = _suite_summaries(baseline)
        assert _suite_summaries(hit)["sp_rope@deg2"] == base["sp_rope@deg2"]

    def test_suite_cache_warm_run_identical(self, tmp_path):
        d = tmp_path / "c"
        cold = Suite(cases=SUITE_CASES, degrees=(2,)).run(workers=0, cache=d)
        assert cold.cache["misses"] == 2 and cold.cache["hits"] == 0
        warm = Suite(cases=SUITE_CASES, degrees=(2,)).run(workers=0, cache=d)
        assert warm.cache["hits"] == 2 and warm.cache["misses"] == 0
        assert _suite_summaries(warm) == _suite_summaries(cold)
        for r in warm:
            assert r.runtime == {"cache": "hit"}

    def test_modelcheck_cache_resume_reproves_only_damaged(self, tmp_path):
        from repro.modelcheck import check_model
        d = tmp_path / "c"
        cold = check_model("gpt", "dp2", workers=0, cache=d)
        assert cold.verdict == "certificate"
        assert cold.cache["misses"] == cold.unique_obligations
        # tear the last journal line (writer crashed mid-commit)
        cache = CertificateCache(d)
        raw = open(cache.journal_path, "rb").read()
        with open(cache.journal_path, "wb") as f:
            f.write(raw[:-10])
        warm = check_model("gpt", "dp2", workers=0, cache=d)
        assert warm.cache["hits"] == cold.unique_obligations - 1
        assert warm.cache["misses"] == 1     # only the torn entry re-proved
        assert warm.cache["recovered_corrupt"] == 1
        assert {k: v["r_o"] for k, v in warm.reports.items()} \
            == {k: v["r_o"] for k, v in cold.reports.items()}

    @pytest.mark.slow
    def test_modelcheck_crash_localized_to_obligation(self, monkeypatch):
        from repro.modelcheck import check_model
        from repro.modelcheck.decompose import decompose
        clean = check_model("gpt", "dp2", workers=0)
        victim = decompose("gpt", "dp2").obset.keys_in_order()[1]
        monkeypatch.setenv(chaos.ENV_SPEC, "crash:1")
        monkeypatch.setenv(chaos.ENV_TARGET, victim)
        rep = check_model("gpt", "dp2", workers=2)
        assert rep.verdict == "error" and not rep.ok
        errored = {b.obligation for b in rep.blocks if b.verdict == "error"}
        assert errored == {victim}           # blame lands on the victim only
        for key, nested in rep.reports.items():
            if key != victim:
                assert nested["verdict"] == clean.reports[key]["verdict"]
                assert nested["r_o"] == clean.reports[key]["r_o"]

    @pytest.mark.slow
    def test_gradcheck_hang_times_out_one_param(self, monkeypatch):
        from repro.gradcheck import check_train
        monkeypatch.setenv(chaos.ENV_SPEC, "hang:1")
        monkeypatch.setenv(chaos.ENV_TARGET, ":w1")
        rep = check_train("dp_accum", workers=2, timeout_s=4.0)
        assert not rep.ok and rep.verdict != "certificate"
        assert rep.failing_params == ["w1"]
        assert rep.reports["w1"]["verdict"] == "timeout"
        assert "budget" in rep.reports["w1"]["error"]
        assert rep.reports["w2"]["verdict"] == "certificate"
