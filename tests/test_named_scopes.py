"""The model's named scopes are metadata only: the compiled decode and train
steps are the same instructions, fusions and layouts with and without them,
once the metadata is stripped.  Instruction names are compared by their
order of appearance: XLA numbers an instruction from its source location,
which a scope can renumber."""
import contextlib
import re
from dataclasses import replace

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.launch.mesh import rules_for_config
from repro.launch.steps import build_decode, build_train
from repro.models.config import InputShape
from repro.models.registry import load_config

METADATA = re.compile(r", metadata=\{[^}]*\}")
NAME = re.compile(r"%([\w.\-]+)")
DEBUG_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")


def _stripped(text: str) -> str:
    """The HLO text without metadata and the source tables it points at,
    each instruction and computation named by its order of appearance."""
    out, skip = [], False
    for line in METADATA.sub("", text).splitlines():
        if line in DEBUG_SECTIONS:
            skip = True
        elif not line.strip():
            skip = False
        if not skip:
            out.append(line)
    names: dict = {}
    return NAME.sub(lambda m: "%" + names.setdefault(m.group(1),
                                                     f"v{len(names)}"),
                    "\n".join(out))


def _compiled(kind: str) -> str:
    jax.clear_caches()           # remat'd blocks keep their traced jaxprs
    cfg = replace(load_config("gpt"), n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab=256)
    mesh = Mesh(np.array(jax.devices("cpu")[:1]).reshape(1, 1),
                ("data", "model"))
    build = build_decode if kind == "decode" else build_train
    fn, abstract, shardings, donate = build(
        cfg, InputShape("tiny", 32, 2, kind), mesh,
        rules_for_config(cfg, mesh))
    return jax.jit(fn, in_shardings=shardings, donate_argnums=donate) \
        .lower(*abstract).compile().as_text()


@pytest.mark.parametrize("kind", ["decode", "train"])
def test_scopes_change_no_compiled_instruction(kind, monkeypatch):
    scoped = _compiled(kind)
    assert 'op_name="jit(fn)/' in scoped and "/mlp/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled(kind)
    assert "/mlp/" not in plain
    assert _stripped(scoped) == _stripped(plain)
