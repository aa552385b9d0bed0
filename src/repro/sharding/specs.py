"""Logical-axis sharding: rules mapping logical tensor axes to mesh axes.

Model code annotates activations/params with *logical* axis names
("batch", "embed", "heads", ...). A ``ShardingRules`` table maps those to
mesh axes; ``logical_to_spec`` builds a PartitionSpec; ``constrain`` applies
``with_sharding_constraint`` when a mesh is active (no-op otherwise, so the
same model code runs in single-device smoke tests, GraphGuard capture, and
512-chip dry-runs).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field, replace
from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Axis = Union[None, str, tuple]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis -> mesh axis (or tuple of mesh axes, or None)."""
    rules: dict

    def spec_for(self, logical_axes: tuple) -> P:
        entries = []
        for ax in logical_axes:
            if ax is None:
                entries.append(None)
            else:
                entries.append(self.rules.get(ax))
        return P(*entries)

    def with_(self, **updates) -> "ShardingRules":
        d = dict(self.rules)
        d.update(updates)
        return ShardingRules(d)


# The baseline production plan: data-parallel batch over (pod, data),
# tensor-parallel model dims over model; parameters ZeRO/FSDP-sharded over
# data on their non-tensor dim ("embed_fsdp" is used for *parameters only*).
def default_rules(multi_pod: bool = False, fsdp: bool = True) -> ShardingRules:
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return ShardingRules({
        "batch": data_axes,
        "seq": None,
        "embed": None,
        "embed_fsdp": "data" if fsdp else None,   # parameter-only dim
        "heads": "model",
        "kv_heads": "model",
        "qheads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "expert_ff": None,
        "expert_fsdp": "data" if fsdp else None,
        "act_ff": "model",       # activation hidden dim (TP)
        "act_heads": "model",    # activation heads dim (TP)
        "layers": None,
        "state": None,
        "kv_seq": None,
        "conv": None,
    })


# ---------------------------------------------------------------------------
# Mesh plans (modelcheck): a named mesh + logical-axis rules in one object
# ---------------------------------------------------------------------------

# Logical-axis rules for the whole-model verification plans: batch over the
# data axis, tensor dims (heads / ff / vocab / experts) over the model axis,
# parameters unsharded on their embed dim (pure Megatron TP — no ZeRO, so
# block programs need no weight gathers).  ``embed_tp`` is the embedding
# table's feature dim: sharding it (rather than vocab) keeps the gather
# local and assembles the activation with one all_gather, staying inside
# the lemma fragment (vocab-parallel embedding needs a value-dependent
# masked gather, which no symbolic engine can verify).
def plan_rules(axes: dict) -> ShardingRules:
    dp = "dp" if "dp" in axes else None
    tp = "tp" if "tp" in axes else None
    return ShardingRules({
        "batch": dp,
        "seq": None,
        "embed": None,
        "embed_fsdp": None,
        "embed_tp": tp,
        "vocab_rows": None,  # embedding-table rows (gather stays local)
        "heads": tp,
        "kv_heads": tp,
        "ff": tp,
        "vocab": tp,
        "experts": tp,
        "act_ff": tp,
        "act_heads": tp,
        "layers": None,
    })


@dataclass(frozen=True)
class MeshPlan:
    """A named sharding plan: ordered mesh axes + logical-axis rules.

    ``repro.modelcheck`` derives every obligation's ``in_specs`` (and thus
    R_i) from the plan: parameter/activation leaf specs carry *logical*
    axis names and ``spec_for`` maps them through the rules."""
    name: str
    axes: tuple                          # (("dp", 2), ("tp", 2)) — ordered
    rules: ShardingRules

    @property
    def mesh_axes(self) -> dict:
        return dict(self.axes)

    @property
    def degree(self) -> tuple:
        return tuple(s for _, s in self.axes)

    def axis(self, name: str) -> int:
        return self.mesh_axes.get(name, 1)

    def spec_for(self, logical_axes: tuple) -> P:
        return self.rules.spec_for(tuple(logical_axes))


PLAN_AXES = ("dp", "tp")


def parse_plan(token: str) -> MeshPlan:
    """Parse a plan token like ``dp2``, ``tp4`` or ``dp2xtp2`` into a
    :class:`MeshPlan` (axis order is as written; sizes must be >= 2 — an
    absent axis is simply not in the mesh)."""
    import re
    axes = []
    for part in str(token).split("x"):
        m = re.fullmatch(r"([a-z]+)(\d+)", part)
        if not m or m.group(1) not in PLAN_AXES:
            raise ValueError(
                f"bad plan {token!r} — expected parts like `dp2`/`tp4` "
                f"joined by `x` (axes: {PLAN_AXES})")
        name, size = m.group(1), int(m.group(2))
        if size < 2:
            raise ValueError(f"bad plan {token!r}: axis {name} needs "
                             f"size >= 2 (drop the axis instead of size 1)")
        if any(a == name for a, _ in axes):
            raise ValueError(f"bad plan {token!r}: duplicate axis {name}")
        axes.append((name, size))
    if not axes:
        raise ValueError(f"bad plan {token!r}: no mesh axes")
    axes = tuple(axes)
    return MeshPlan(token, axes, plan_rules(dict(axes)))


# The named plans the modelcheck CLI/benchmarks sweep by default.  tp4 parses
# but is a documented scale limit (the 4-wide psum chains hit the same
# assoc/comm blowup as tp_dp_2d@(4,4) — see EXPERIMENTS.md §Gaps).
DEFAULT_PLANS = ("dp2", "tp2", "dp2xtp2", "dp4")


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[ShardingRules] = None


_ctx = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[ShardingRules]):
    prev = (_ctx.mesh, _ctx.rules)
    _ctx.mesh, _ctx.rules = mesh, rules
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def logical_sharding(logical_axes: tuple) -> Optional[NamedSharding]:
    """The sharding the active rules give ``logical_axes`` on the active
    mesh; None when no mesh is active."""
    if _ctx.mesh is None or _ctx.rules is None:
        return None
    return NamedSharding(_ctx.mesh, _ctx.rules.spec_for(logical_axes))


def constrain(x, logical_axes: tuple):
    """Apply a sharding constraint if a mesh is active; identity otherwise."""
    sharding = logical_sharding(logical_axes)
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def _is_axes_leaf(x):
    """A logical-axes leaf is a tuple of axis names / None — NOT a tuple of
    tuples (e.g. a (k, v) cache pair), which is tree structure."""
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def tree_shardings(mesh: Mesh, rules: ShardingRules, logical_tree):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, rules.spec_for(axes)),
        logical_tree, is_leaf=_is_axes_leaf)
