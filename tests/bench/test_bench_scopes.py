"""Device time per program scope: the program's compiled steps name their
work, and the reduction from a trace and the step's HLO sums it per scope,
on traces that need no chip."""
import json
import re
from pathlib import Path

import pytest

from conftest import TINY_DENSE, harness

import devtrace  # noqa: E402
import scopes  # noqa: E402

MS = 1_000_000.0          # nanoseconds
DATA = Path(__file__).parent / "data"


def test_scope_of_takes_the_innermost_and_peels_wrappers():
    back = ("jit(fn)/transpose(jvp(layers))/while/body/closed_call/"
            "checkpoint/rematted_computation/mlp/jit(silu)/mul")
    assert scopes.scope_of(back) == "mlp"
    assert scopes.scope_of("jit(g)/layers/while/body/closed_call/attn/"
                           "kv_cache/dynamic_update_slice") == "kv_cache"
    assert scopes.scope_of("jit(g)/jvp(head)/dot_general") == "head"
    assert scopes.scope_of("jit(greedy)/argmax") is None


SYNTHETIC_HLO = """HloModule jit_step, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %mm = f32[8]{0} fusion(%p), kind=kOutput, calls=%fused_mm, metadata={op_name="jit(step)/layers/while/body/attn/attend/dot_general"}
  ROOT %copy.1 = (s32[], f32[8]) copy(%mm)
}

%fused_mm (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %dot.1 = f32[8]{0} dot(%q, %q), metadata={op_name="jit(step)/layers/while/body/attn/attend/dot_general"}
}

%cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]) parameter(0)
  ROOT %lt = pred[] compare(%c, %c), direction=LT, metadata={op_name="jit(step)/layers/while/cond/lt"}
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.5 = (s32[], f32[8]) while(%x), condition=%cond, body=%body, metadata={op_name="jit(step)/layers/while"}
  %gte = f32[8]{0} get-tuple-element(%while.5), index=1
  %copy.9 = f32[8]{0} copy(%gte)
  ROOT %argmax = f32[8]{0} fusion(%copy.9), kind=kLoop, calls=%fused_mm, metadata={op_name="jit(step)/argmax"}
}
"""


def test_hlo_scopes_inherit_from_the_caller_and_the_copied_value():
    module, table = scopes.hlo_scopes(SYNTHETIC_HLO)
    assert module == "jit_step"
    assert table["while.5"] == ("while", "layers")
    assert table["mm"] == ("fusion", "attend")        # innermost of attn
    assert table["copy.1"] == ("copy", "layers")      # no op_name: caller
    assert table["copy.9"] == ("copy", "layers")      # entry: its operand
    assert table["argmax"] == ("fusion", None)        # named, no scope


def test_synthetic_trace_counts_leaves_once():
    """A while holding two operations: the while is not counted, the
    fusion lands in its innermost scope, the copy without metadata in its
    caller's, the rest in ``(unscoped)``; the window clips."""
    module, table = scopes.hlo_scopes(SYNTHETIC_HLO)
    tr = {"devices": {"/device:TPU:0": [
              ("module:jit_step(77)", 0.0, 10 * MS),
              ("%while.5 = (s32[], f32[8]) while(...)", 1 * MS, 6 * MS),
              ("mm", 1 * MS, 4 * MS), ("copy.1", 4 * MS, 6 * MS),
              ("argmax", 6 * MS, 7 * MS),
              ("module:jit_other(5)", 11 * MS, 13 * MS),
              ("mm", 11 * MS, 13 * MS)]},
          "host": [("window", 0.0, 12 * MS)]}
    got = scopes.reduce(tr, {module: table})
    assert set(got) == set(scopes.SCOPES) | {scopes.UNSCOPED}
    assert got["attend"] == pytest.approx(0.003)
    assert got["layers"] == pytest.approx(0.002)
    # argmax (1 ms) and the other program's op, clipped at 12 ms (1 ms)
    assert got[scopes.UNSCOPED] == pytest.approx(0.002)
    assert got["attn"] == got["mlp"] == 0.0
    ms = scopes.per_step_ms(got, 2)
    assert ms["attn_ms"] == pytest.approx(1.5)
    assert ms["scan_ms"] == pytest.approx(1.0)


def test_recorded_v5e_decode_steps():
    """Three steps of ``yi-9b.decode`` recorded on a TPU v5 lite
    (``devtrace.extract`` output, instruction names only) with the step's
    HLO text, cut to its computations and ``op_name`` metadata."""
    rec = json.loads((DATA / "v5e_decode_scopes.json").read_text())
    module, table = scopes.hlo_scopes(rec.pop("hlo"))
    assert module == "jit_greedy"
    got = scopes.reduce(rec, {module: table})
    busy = devtrace.reduce(rec)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy, rel=0.05)
    assert got[scopes.UNSCOPED] < 0.1 * busy
    for s in ("embed", "layers", "attn", "kv_cache", "attend", "mlp",
              "head"):
        assert got[s] > 0, s
    assert got["optimizer"] == 0.0


def _compiled_text(kind: str) -> str:
    import jax
    from repro.launch.mesh import rules_for_config
    from repro.launch.steps import build_decode, build_train
    from repro.models.config import InputShape
    cfg = harness.program_config(dict(TINY_DENSE, program_config="gpt"))
    mesh = harness.mesh_of(jax.devices("cpu")[:1], (1, 1))
    build = build_decode if kind == "decode" else build_train
    shape = InputShape("tiny", 32, 2, kind)
    fn, abstract, shardings, donate = build(cfg, shape, mesh,
                                            rules_for_config(cfg, mesh))
    return jax.jit(fn, in_shardings=shardings, donate_argnums=donate) \
        .lower(*abstract).compile().as_text()


@pytest.mark.parametrize("kind", ["decode", "train"])
def test_compiled_steps_name_their_work(kind):
    text = _compiled_text(kind)
    _, table = scopes.hlo_scopes(text)
    dots = {n: s for n, (op, s) in table.items()
            if op in ("dot", "convolution")}
    assert dots and all(dots.values()), dots
    found = {s for _, s in table.values()}
    assert {"attn", "mlp", "head", "layers"} <= found
    if kind == "decode":
        assert "kv_cache" in found and "optimizer" not in found
        assert any(s == "kv_cache" for op, s in table.values()
                   if op == "dynamic-update-slice")
    else:
        assert "optimizer" in found
        back = [m.group(1) for m in re.finditer(
            r'%([\w.\-]+) = \S+ dot\([^\n]*op_name="[^"]*transpose\('
            r'[^"]*/mlp/', text)]
        assert back and all(table[n][1] == "mlp" for n in back)
