"""Documentation gates: the lemma catalog, the CLI reference, and the
docstring ruleset are enforced here so docs cannot drift from code."""
import os
import re
import subprocess
import sys

from repro.core.lemmas import LEMMAS, all_lemmas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return f.read()


# ---------------------------------------------------------------------------
# docs/LEMMAS.md — every lemma has a catalog entry, and vice versa
# ---------------------------------------------------------------------------

def _catalog_names():
    return set(re.findall(r"^### `([a-z0-9_]+)`", _read("docs", "LEMMAS.md"),
                          flags=re.MULTILINE))


def test_every_lemma_is_catalogued():
    documented = _catalog_names()
    missing = {l.name for l in LEMMAS} - documented
    assert not missing, f"lemmas without a docs/LEMMAS.md entry: {missing}"


def test_no_stale_catalog_entries():
    stale = _catalog_names() - {l.name for l in all_lemmas()}
    assert not stale, f"docs/LEMMAS.md entries for unknown lemmas: {stale}"


def test_lemma_entries_state_trigger_ops_and_source():
    doc = _read("docs", "LEMMAS.md")
    for lemma in LEMMAS:
        m = re.search(rf"^### `{lemma.name}`([^\n]*)", doc, flags=re.M)
        heading = m.group(1)
        assert "ops:" in heading and "source:" in heading, lemma.name
        assert getattr(lemma, "source", "builtin") in heading, lemma.name


# ---------------------------------------------------------------------------
# docs/CLI.md — the --help block tracks the real argparse surface
# ---------------------------------------------------------------------------

def test_cli_help_block_in_sync():
    r = subprocess.run([sys.executable,
                        os.path.join(ROOT, "scripts", "check_cli_docs.py")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_doc_covers_all_paths_and_exit_codes():
    doc = _read("docs", "CLI.md")
    for flag in ("--case", "--model", "--train", "--serve", "--fn",
                 "--json", "--list"):
        assert flag in doc, flag
    for env in ("GRAPHGUARD_OPT", "GRAPHGUARD_CACHE_DIR", "GRAPHGUARD_CHAOS"):
        assert env in doc, env
    assert '"schema_version": 2' in doc


# ---------------------------------------------------------------------------
# docstring ruleset over repro.core + repro.api
# ---------------------------------------------------------------------------

def test_docstring_coverage_gate():
    r = subprocess.run([sys.executable,
                        os.path.join(ROOT, "scripts", "check_docstrings.py")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# ARCHITECTURE.md — package sections and live cross-links
# ---------------------------------------------------------------------------

def test_architecture_covers_every_subsystem():
    doc = _read("ARCHITECTURE.md")
    for pkg in ("repro.core", "repro.api", "repro.runtime",
                "repro.modelcheck", "repro.gradcheck", "repro.servecheck",
                "repro.obs"):
        assert pkg in doc, pkg


def test_architecture_links_resolve():
    doc = _read("ARCHITECTURE.md")
    for target in set(re.findall(r"\]\(([^)#]+)\)", doc)):
        if "://" in target:
            continue
        assert os.path.exists(os.path.join(ROOT, target)), \
            f"ARCHITECTURE.md links to missing path {target}"


# ---------------------------------------------------------------------------
# docs/OBSERVABILITY.md — metric names and span taxonomy track the code
# ---------------------------------------------------------------------------

def _source_metric_names():
    names = set()
    for dirpath, _dirs, files in os.walk(os.path.join(ROOT, "src", "repro")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                src = f.read()
            names |= set(re.findall(
                r'REGISTRY\.(?:counter|histogram)\(\s*"([a-z_.]+)"', src))
    return names


def test_observability_doc_covers_every_live_metric():
    doc = _read("docs", "OBSERVABILITY.md")
    documented = set(re.findall(r"`([a-z_]+\.[a-z_]+)`", doc))
    live = _source_metric_names()
    assert live, "no REGISTRY.counter/histogram call sites found in src"
    missing = live - documented
    assert not missing, \
        f"metrics without a docs/OBSERVABILITY.md entry: {missing}"


def test_observability_doc_names_key_spans():
    doc = _read("docs", "OBSERVABILITY.md")
    for name in ("capture", "infer", "saturate", "extract", "task",
                 "queue", "run", "saturate.batch", "cache.probe",
                 "task.retry", "task.timeout", "pool.degraded",
                 "decompose", "expand", "seam", "stitch"):
        assert f"`{name}`" in doc, name


def test_observability_doc_names_every_device_scope():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
    import scopes
    doc = _read("docs", "OBSERVABILITY.md")
    for name in scopes.SCOPES:
        assert f"| `{name}` |" in doc, name
