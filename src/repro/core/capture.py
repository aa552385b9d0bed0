"""Computation-graph capture: jaxpr -> GraphGuard Graph IR.

The paper's capture layer is TorchDynamo (§5.1); ours is ``jax.make_jaxpr``.
Two capture paths:

  * ``capture(fn, avals, names)`` — the sequential model ``G_s``.
  * ``capture_spmd(fn, mesh_axes, in_specs, avals, names)`` — the distributed
    implementation as a shard_map program. The inner jaxpr is the *per-rank*
    SPMD program with explicit collective primitives (psum / all_gather /
    reduce_scatter / all_to_all / ppermute / axis_index). ``expand_spmd``
    instantiates it once per rank coordinate, folding ``axis_index`` to a
    literal and translating each collective into *pure cross-rank ops*:

        psum            ->  add over the rank group
        all_gather      ->  concat over the rank group
        reduce_scatter  ->  slice(add over group, rank block)
        all_to_all      ->  concat of per-source slices
        ppermute        ->  renaming (or zeros for uncovered ranks)

    so the lemma engine never needs to know about communication.

Primitive normalization maps jaxpr primitives to the small op vocabulary in
``terms.py``; ``dot_general`` is canonicalized to ``matmul``/``bmm`` with
explicit transposes/reshapes; ``pad`` becomes concat-with-zero-blocks (which
is what makes pad/slice mismatch bugs provable); scalar operands are lifted
to explicit ``broadcast`` so elementwise lemmas stay shape-uniform.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import numpy as np

import jax
import jax.extend.core  # noqa: F401  (jax.extend requires explicit import)
import jax.numpy as jnp
from jax.sharding import AbstractMesh, AxisType, PartitionSpec

from . import terms as T
from .terms import Term


# ---------------------------------------------------------------------------
# Graph IR
# ---------------------------------------------------------------------------

@dataclass
class Graph:
    """Straight-line tensor program: ordered ``defs`` of name := Term(leaves
    are previously-defined names / inputs / consts / literals)."""
    inputs: list
    outputs: list
    defs: list          # [(name, Term)]
    shapes: dict        # name -> shape tuple
    dtypes: dict        # name -> 'f' | 'i' | 'b'
    consts: dict = field(default_factory=dict)   # name -> np.ndarray

    def tensor(self, name: str) -> Term:
        return T.tensor(name, self.shapes[name], self.dtypes[name])

    @property
    def n_ops(self) -> int:
        return len(self.defs)


def _dt(dtype) -> str:
    k = np.dtype(dtype).kind
    return {"f": "f", "b": "b", "i": "i", "u": "i", "V": "f"}.get(k, "f")


# ---------------------------------------------------------------------------
# Capture driver
# ---------------------------------------------------------------------------

# Strict-mode hook stack (installed by ``from_jaxpr.strict_capture``): each
# entry is called as ``hook(eqn, reason)`` right before a lenient fallback —
# an unknown primitive becoming an opaque term, or an over-budget scan
# raising a bare CaptureError — so the generic frontend can raise a
# structured ``UnsupportedPrimitive`` naming the eqn and its source location.
_EQN_HOOKS: list = []


def _on_unsupported(eqn, reason: str) -> None:
    """Notify strict-mode hooks that ``eqn`` has no clean term lowering."""
    for hook in reversed(_EQN_HOOKS):
        hook(eqn, reason)


class _Namer:
    def __init__(self):
        self.n = 0
        self.map = {}

    def of(self, var) -> str:
        if var not in self.map:
            self.map[var] = f"t{self.n}"
            self.n += 1
        return self.map[var]

    def fresh(self) -> str:
        nm = f"t{self.n}"
        self.n += 1
        return nm

    def set(self, var, name):
        self.map[var] = name


def capture(fn: Callable, avals: Sequence, names: Sequence[str],
            graph_tag: str = "") -> Graph:
    """Capture ``fn(*args)`` into a Graph. ``avals`` are ShapeDtypeStructs."""
    closed = jax.make_jaxpr(fn)(*avals)
    return _jaxpr_to_graph(closed, list(names), graph_tag)


def capture_chain(stages, init_avals, init_names):
    """Capture a *named-block sequence* instead of one opaque jaxpr.

    ``stages`` is a list of ``(name, fn, extra_avals, extra_names)``; stage
    *k* is traced as ``fn(*carry, *extras)`` where ``carry`` is the previous
    stage's output avals (the model activations flowing block to block) and
    ``extras`` are the stage's own parameters.  Carried tensors are named
    ``{stage}.out{j}`` and parameters ``{stage}.{param}``, so graph *k+1*'s
    input names are exactly graph *k*'s output names — the seam contract
    ``repro.modelcheck`` verifies per block.

    Returns ``(graphs, carry_avals, carry_names)`` where ``graphs`` is the
    ordered ``[(stage name, Graph)]`` list and the carry reflects the final
    stage's outputs.
    """
    carry_avals = list(init_avals)
    carry_names = list(init_names)
    graphs = []
    for name, fn, extra_avals, extra_names in stages:
        avals = carry_avals + list(extra_avals)
        names = carry_names + [f"{name}.{n}" for n in extra_names]
        g = capture(fn, avals, names)
        out_shape = jax.eval_shape(fn, *avals)
        leaves = jax.tree_util.tree_leaves(out_shape)
        carry_avals = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves]
        carry_names = [f"{name}.out{j}" for j in range(len(leaves))]
        graphs.append((name, g))
    return graphs, carry_avals, carry_names


@dataclass
class SpmdCapture:
    """A traced per-rank SPMD program before rank expansion: the single-rank
    graph (collectives still symbolic) plus the mesh and input specs
    ``expand_spmd`` needs to instantiate it per rank and derive R_i."""
    graph: Graph                  # per-rank program with collective ops
    mesh_axes: dict               # axis name -> size
    in_specs: list                # PartitionSpec per input
    names: list


def capture_spmd(fn: Callable, mesh_axes: dict, in_specs: Sequence,
                 avals: Sequence, names: Sequence[str]) -> SpmdCapture:
    """Trace a per-rank SPMD ``fn`` under ``shard_map`` on an abstract mesh
    and lower the unwrapped body to a single-rank :class:`Graph` (collectives
    kept as symbolic ops for ``expand_spmd`` to instantiate)."""
    axis_names = tuple(mesh_axes)
    mesh = AbstractMesh(tuple(mesh_axes.values()), axis_names,
                        axis_types=(AxisType.Auto,) * len(axis_names))
    sm = jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=PartitionSpec(), check_vma=False)
    closed = jax.make_jaxpr(sm)(*avals)
    # unwrap the single shard_map eqn
    eqn = None
    for e in closed.jaxpr.eqns:
        if e.primitive.name == "shard_map":
            eqn = e
            break
    assert eqn is not None, "expected a shard_map eqn"
    inner = eqn.params["jaxpr"]   # open jaxpr, per-rank avals

    # Closed-over consts of fn appear as extra leading eqn invars: align
    # names/specs per eqn invar, and mark const positions.
    outer_pos = {v: i for i, v in enumerate(closed.jaxpr.invars)}
    const_map = dict(zip(closed.jaxpr.constvars, closed.consts))
    eqn_specs = list(eqn.params["in_specs"])
    inner_names, const_positions = [], {}
    arg_names, arg_specs = [], []
    for pos, atom in enumerate(eqn.invars):
        if isinstance(atom, jax.extend.core.Literal):
            const_positions[pos] = np.asarray(atom.val)
            inner_names.append(f"cin{pos}")
            continue
        if atom in outer_pos:
            nm = names[outer_pos[atom]]
            inner_names.append(nm)
            arg_names.append(nm)
            arg_specs.append(eqn_specs[pos])
        elif atom in const_map:
            const_positions[pos] = np.asarray(const_map[atom])
            inner_names.append(f"cin{pos}")
        else:
            raise CaptureError(
                "shard_map operand computed by outer ops — trace the "
                "distributed fn directly (no outer transformations)")
    inner_closed = jax.extend.core.ClosedJaxpr(inner, ())
    g = _jaxpr_to_graph(inner_closed, inner_names, "")
    for pos, val in const_positions.items():
        nm = inner_names[pos]
        g.consts[nm] = val
        g.inputs.remove(nm)
    return SpmdCapture(g, dict(mesh_axes), list(arg_specs), list(arg_names))


def _jaxpr_to_graph(closed, names, tag) -> Graph:
    jaxpr = closed.jaxpr
    namer = _Namer()
    g = Graph([], [], [], {}, {}, {})

    def declare(var, name=None):
        nm = name or namer.of(var)
        namer.set(var, nm)
        g.shapes[nm] = tuple(var.aval.shape)
        g.dtypes[nm] = _dt(var.aval.dtype)
        return nm

    for i, v in enumerate(jaxpr.invars):
        nm = declare(v, names[i] if i < len(names) else None)
        g.inputs.append(nm)
    for i, (cv, cval) in enumerate(zip(jaxpr.constvars, closed.consts)):
        nm = declare(cv, f"const{i}{tag}")
        g.consts[nm] = np.asarray(cval)

    env: dict = {}

    def read(atom) -> Term:
        if isinstance(atom, jax.extend.core.Literal):
            v = atom.val
            if np.ndim(v) == 0:
                return T.lit(v.item() if hasattr(v, "item") else v)
            nm = f"lconst{len(g.consts)}{tag}"
            g.consts[nm] = np.asarray(v)
            g.shapes[nm] = tuple(np.shape(v))
            g.dtypes[nm] = _dt(np.asarray(v).dtype)
            return g.tensor(nm)
        nm = namer.of(atom)
        return T.tensor(nm, tuple(atom.aval.shape), _dt(atom.aval.dtype))

    def emit(var, term: Term):
        nm = declare(var)
        assert term.shape == tuple(var.aval.shape), \
            f"{var.aval.shape} vs {term.shape} for {term.op}"
        g.defs.append((nm, term))

    _process_eqns(jaxpr.eqns, read, emit, g, namer, declare)

    for v in jaxpr.outvars:
        if isinstance(v, jax.extend.core.Literal):
            nm = f"outlit{len(g.consts)}"
            g.consts[nm] = np.asarray(v.val)
            g.shapes[nm] = tuple(np.shape(v.val))
            g.dtypes[nm] = _dt(np.asarray(v.val).dtype)
            g.outputs.append(nm)
        else:
            g.outputs.append(namer.of(v))
    return g


def _process_eqns(eqns, read, emit, g, namer, declare):
    for eqn in eqns:
        prim = eqn.primitive.name
        # -- structural inlining ------------------------------------------
        if prim in ("pjit", "jit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
                    "checkpoint", "custom_jvp_call_jaxpr", "core_call"):
            sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr"))
            if hasattr(sub, "jaxpr"):  # ClosedJaxpr
                consts = sub.consts
                sub = sub.jaxpr
            else:
                consts = ()
            # Scoped inlining: the same sub-jaxpr may be inlined repeatedly
            # (e.g. silu's custom_jvp), so its vars must NOT share global
            # name bindings — use a local env overlay.
            env_map: dict = {}
            for cv, cval in zip(sub.constvars, consts):
                nm = f"iconst{len(g.consts)}"
                g.consts[nm] = np.asarray(cval)
                g.shapes[nm] = tuple(cv.aval.shape)
                g.dtypes[nm] = _dt(cv.aval.dtype)
                env_map[cv] = T.tensor(nm, tuple(cv.aval.shape),
                                       _dt(cv.aval.dtype))
            for iv, atom in zip(sub.invars, eqn.invars):
                env_map[iv] = read(atom)

            def rd(atom, env_map=env_map):
                if isinstance(atom, jax.extend.core.Literal):
                    return read(atom)
                if atom in env_map:
                    return env_map[atom]
                return read(atom)

            def em(var, term, env_map=env_map):
                nm = namer.fresh()
                g.shapes[nm] = term.shape
                g.dtypes[nm] = term.dtype
                g.defs.append((nm, term))
                env_map[var] = T.tensor(nm, term.shape, term.dtype)

            _process_eqns(sub.eqns, rd, em, g, namer, declare)
            for ov, iv in zip(eqn.outvars, sub.outvars):
                tm = rd(iv)
                if tm.op == "tensor":
                    namer.set(ov, tm.name)
                    g.shapes[tm.name] = tm.shape
                    g.dtypes[tm.name] = tm.dtype
                else:
                    emit(ov, tm)
            continue
        if prim == "scan":
            _inline_scan(eqn, read, emit, g, namer, declare)
            continue
        # -- regular primitive --------------------------------------------
        try:
            outs = _normalize(eqn, read)
        except CaptureError as e:
            # a partially-supported primitive (e.g. interior padding) — let
            # strict mode attach the eqn + source location before the raise
            _on_unsupported(eqn, str(e))
            raise
        if outs is None:
            # uninterpreted: keep as opaque op (user lemma extension point)
            _on_unsupported(eqn, "no normalization to the term vocabulary")
            args = tuple(read(a) for a in eqn.invars)
            for k, ov in enumerate(eqn.outvars):
                tag = f"#{k}" if len(eqn.outvars) > 1 else ""
                emit(ov, T.opaque(prim + tag, args, tuple(ov.aval.shape),
                                  _dt(ov.aval.dtype)))
        else:
            assert len(outs) == len(eqn.outvars), prim
            for ov, tm in zip(eqn.outvars, outs):
                emit(ov, tm)


def _inline_scan(eqn, read, emit, g, namer, declare):
    p = eqn.params
    length, nc, ncar = p["length"], p["num_consts"], p["num_carry"]
    if length > 8:
        _on_unsupported(eqn, f"scan of length {length} exceeds the unroll "
                             f"budget of 8")
        raise CaptureError(
            f"scan of length {length} in a verification graph — unroll "
            f"explicitly or verify a single layer (paper §6.3 verifies one "
            f"layer; so do we)")
    closed = p["jaxpr"]
    consts_in = eqn.invars[:nc]
    carry_in = eqn.invars[nc:nc + ncar]
    xs_in = eqn.invars[nc + ncar:]
    carry_terms = [read(a) for a in carry_in]
    ys_acc: list = [[] for _ in range(len(eqn.outvars) - ncar)]
    for it in range(length):
        sub = closed.jaxpr
        local = _Namer()
        env_map = {}
        for cv, cval in zip(sub.constvars, closed.consts):
            nm = f"sconst{len(g.consts)}"
            g.consts[nm] = np.asarray(cval)
            g.shapes[nm] = tuple(cv.aval.shape)
            g.dtypes[nm] = _dt(cv.aval.dtype)
            env_map[cv] = T.tensor(nm, tuple(cv.aval.shape), _dt(cv.aval.dtype))
        invars = sub.invars
        for v, a in zip(invars[:nc], consts_in):
            env_map[v] = read(a)
        for v, t in zip(invars[nc:nc + ncar], carry_terms):
            env_map[v] = t
        for v, a in zip(invars[nc + ncar:], xs_in):
            xs_t = read(a)
            sl = T.slice_(xs_t, (it,) + (0,) * (len(xs_t.shape) - 1),
                          (it + 1,) + xs_t.shape[1:])
            env_map[v] = T.reshape(sl, xs_t.shape[1:])

        def rd(atom, env_map=env_map):
            if isinstance(atom, jax.extend.core.Literal):
                return read(atom)
            if atom in env_map:
                return env_map[atom]
            return read(atom)

        def em(var, term, env_map=env_map):
            env_map[var] = term
            nm = declare(var, f"{namer.of(var)}.i{it}")
            g.shapes[nm] = term.shape
            g.dtypes[nm] = term.dtype
            g.defs.append((nm, term))
            env_map[var] = T.tensor(nm, term.shape, term.dtype)

        _process_eqns(sub.eqns, rd, em, g, namer, declare)
        outs = [rd(v) for v in sub.outvars]
        carry_terms = outs[:ncar]
        for j, y in enumerate(outs[ncar:]):
            ys_acc[j].append(T.reshape(y, (1,) + y.shape))
    for ov, t in zip(eqn.outvars[:ncar], carry_terms):
        emit(ov, t)
    for ov, pieces in zip(eqn.outvars[ncar:], ys_acc):
        emit(ov, T.concat(pieces, 0))


class CaptureError(RuntimeError):
    """A jaxpr could not be lowered to the term language (e.g. an
    over-budget scan or an unsupported primitive configuration)."""


# ---------------------------------------------------------------------------
# Primitive normalization
# ---------------------------------------------------------------------------

_EW1_MAP = {
    "neg": "neg", "exp": "exp", "log": "log", "tanh": "tanh",
    "logistic": "logistic", "rsqrt": "rsqrt", "sqrt": "sqrt", "sin": "sin",
    "cos": "cos", "abs": "abs", "erf": "erf", "floor": "floor",
    "sign": "sign", "stop_gradient": "stop_grad", "log1p": "log1p",
    "expm1": "expm1", "not": "not", "copy": None, "reduce_precision": None,
}
_EW2_MAP = {
    "add": "add", "add_any": "add", "sub": "sub", "mul": "mul", "div": "div", "max": "max2",
    "min": "min2", "pow": "pow", "eq": "eq", "ne": "ne", "lt": "lt",
    "le": "le", "gt": "gt", "ge": "ge", "and": "and", "or": "or",
    "rem": "rem", "atan2": "atan2", "nextafter": "nextafter",
    "shift_left": "shift_left", "shift_right_logical": "shift_right",
    "shift_right_arithmetic": "shift_right",
}

COLLECTIVES = {"psum", "psum_invariant", "all_gather", "reduce_scatter",
               "all_to_all", "ppermute", "pvary", "axis_index", "pbroadcast"}


def _lift(t: Term, shape) -> Term:
    """Broadcast scalars/size-1 dims so ew2 operands are shape-uniform."""
    shape = tuple(shape)
    if t.shape == shape or shape == ():
        return t
    if t.shape == ():
        return T.broadcast(t, shape, ())
    if len(t.shape) == len(shape) and all(
            td == sd or td == 1 for td, sd in zip(t.shape, shape)):
        return T.broadcast(t, shape, tuple(range(len(shape))))
    raise AssertionError(f"cannot lift {t.shape} to {shape}")


def _normalize(eqn, read) -> Optional[list]:
    """Return output Terms for an eqn, or None -> opaque."""
    prim = eqn.primitive.name
    p = eqn.params
    out_aval = eqn.outvars[0].aval if eqn.outvars else None

    if prim == "device_put":  # layout/transfer no-op in a verification graph
        return [read(a) for a in eqn.invars]
    if prim in _EW1_MAP:
        x = read(eqn.invars[0])
        mapped = _EW1_MAP[prim]
        return [x] if mapped is None else [T.ew1(mapped, x)]
    if prim == "integer_pow":
        return [T.integer_pow(read(eqn.invars[0]), p["y"])]
    if prim == "square":
        return [T.integer_pow(read(eqn.invars[0]), 2)]
    if prim in _EW2_MAP:
        a, b = read(eqn.invars[0]), read(eqn.invars[1])
        sh = tuple(out_aval.shape)
        return [T.ew2(_EW2_MAP[prim], _lift(a, sh), _lift(b, sh))]
    if prim == "select_n":
        which = read(eqn.invars[0])
        cases = [read(a) for a in eqn.invars[1:]]
        if len(cases) != 2:
            return None
        sh = tuple(out_aval.shape)
        # select_n(pred, a, b) = b where pred else a  (pred indexes cases!)
        return [T.select(_lift(which, sh), _lift(cases[1], sh),
                         _lift(cases[0], sh))]
    if prim == "clamp":
        lo, x, hi = (read(a) for a in eqn.invars)
        sh = tuple(out_aval.shape)
        return [T.ew2("max2", T.ew2("min2", _lift(x, sh), _lift(hi, sh)),
                      _lift(lo, sh))]
    if prim == "convert_element_type":
        return [T.convert(read(eqn.invars[0]), _dt(p["new_dtype"]))]
    if prim == "broadcast_in_dim":
        x = read(eqn.invars[0])
        return [T.broadcast(x, tuple(p["shape"]),
                            tuple(p["broadcast_dimensions"]))]
    if prim == "reshape":
        return [T.reshape(read(eqn.invars[0]), tuple(p["new_sizes"]))]
    if prim == "squeeze":
        return [T.reshape(read(eqn.invars[0]), tuple(out_aval.shape))]
    if prim == "expand_dims":
        return [T.reshape(read(eqn.invars[0]), tuple(out_aval.shape))]
    if prim == "transpose":
        return [T.transpose(read(eqn.invars[0]), tuple(p["permutation"]))]
    if prim == "rev":
        return [T.rev(read(eqn.invars[0]), tuple(p["dimensions"]))]
    if prim == "concatenate":
        return [T.concat([read(a) for a in eqn.invars], p["dimension"])]
    if prim == "slice":
        if p.get("strides") and any(s != 1 for s in p["strides"]):
            return None
        return [T.slice_(read(eqn.invars[0]), tuple(p["start_indices"]),
                         tuple(p["limit_indices"]))]
    if prim == "split":
        x = read(eqn.invars[0])
        axis = p["axis"]
        outs = []
        off = 0
        for sz in p["sizes"]:
            starts = tuple(off if i == axis else 0
                           for i in range(len(x.shape)))
            limits = tuple(off + sz if i == axis else x.shape[i]
                           for i in range(len(x.shape)))
            outs.append(T.slice_(x, starts, limits))
            off += sz
        return outs
    if prim == "iota":
        return [T.iota(tuple(p["shape"]), p["dimension"], _dt(p["dtype"]))]
    if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                "reduce_and", "reduce_or"):
        return [T.reduce_(f"reduce_{prim.split('_')[1]}", read(eqn.invars[0]),
                          tuple(int(a) for a in p["axes"]))]
    if prim in ("argmax", "argmin"):
        axes = p["axes"]
        if len(axes) != 1:
            return None
        return [T.argmax(read(eqn.invars[0]), axes[0])] if prim == "argmax" \
            else None
    if prim == "cumsum":
        return [T.cumsum(read(eqn.invars[0]), p["axis"])]
    if prim == "dot_general":
        return [_norm_dot(eqn, read)]
    if prim == "dynamic_slice":
        x = read(eqn.invars[0])
        starts = tuple(read(a) for a in eqn.invars[1:])
        if all(s.op == "lit" for s in starts):
            st = tuple(int(s.value) for s in starts)
            st = tuple(min(max(s, 0), d - z)
                       for s, d, z in zip(st, x.shape, p["slice_sizes"]))
            return [T.slice_(x, st, tuple(s + z for s, z in
                                          zip(st, p["slice_sizes"])))]
        return [Term("dyn_slice", (x,) + starts,
                     (("sizes", tuple(p["slice_sizes"])),),
                     tuple(p["slice_sizes"]), x.dtype)]
    if prim == "dynamic_update_slice":
        x, u = read(eqn.invars[0]), read(eqn.invars[1])
        starts = tuple(read(a) for a in eqn.invars[2:])
        if all(s.op == "lit" for s in starts):
            st = tuple(min(max(int(s.value), 0), d - z)
                       for s, d, z in zip(starts, x.shape, u.shape))
            return [T.dus(x, u, st)]
        return [Term("dyn_update_slice", (x, u) + starts, (), x.shape, x.dtype)]
    if prim == "pad":
        return [_norm_pad(eqn, read)]
    if prim == "gather":
        return _norm_gather(eqn, read)
    if prim in COLLECTIVES:
        return _norm_collective(eqn, read)
    if prim == "scatter-add" or prim == "scatter_add":
        x, idx, upd = (read(a) for a in eqn.invars)
        dn = p["dimension_numbers"]
        return [Term("scatter_add", (x, idx, upd),
                     (("dnums", repr(dn)),), x.shape, x.dtype)]
    return None


def _norm_dot(eqn, read) -> Term:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    a, b = read(eqn.invars[0]), read(eqn.invars[1])
    la, lb_n = len(a.shape), len(b.shape)
    lfree = [i for i in range(la) if i not in lc and i not in lb]
    rfree = [i for i in range(lb_n) if i not in rc and i not in rb]

    if not lb:  # no batch dims: general matmul (..., k) x (k, n)
        # lhs -> (lfree..., K)
        perm_a = tuple(lfree) + tuple(lc)
        ta = T.transpose(a, perm_a)
        if len(lc) > 1:
            k = int(np.prod([a.shape[i] for i in lc], dtype=np.int64))
            ta = T.reshape(ta, tuple(a.shape[i] for i in lfree) + (k,))
        # rhs -> (K, rfree...)
        perm_b = tuple(rc) + tuple(rfree)
        tb = T.transpose(b, perm_b)
        k = ta.shape[-1]
        nfree = tuple(b.shape[i] for i in rfree)
        n = int(np.prod(nfree, dtype=np.int64)) if nfree else 1
        tb = T.reshape(tb, (k, n))
        out = Term("matmul", (ta, tb), (), ta.shape[:-1] + (n,), a.dtype)
        final = tuple(a.shape[i] for i in lfree) + nfree
        return T.reshape(out, final)

    # batch case -> bmm (B..., M, K) x (B..., K, N)
    perm_a = tuple(lb) + tuple(lfree) + tuple(lc)
    ta = T.transpose(a, perm_a)
    bshape = tuple(a.shape[i] for i in lb)
    m = int(np.prod([a.shape[i] for i in lfree], dtype=np.int64)) if lfree else 1
    k = int(np.prod([a.shape[i] for i in lc], dtype=np.int64))
    ta = T.reshape(ta, bshape + (m, k))
    perm_b = tuple(rb) + tuple(rc) + tuple(rfree)
    tb = T.transpose(b, perm_b)
    nfree = tuple(b.shape[i] for i in rfree)
    n = int(np.prod(nfree, dtype=np.int64)) if nfree else 1
    tb = T.reshape(tb, bshape + (k, n))
    out = T.bmm(ta, tb)
    final = bshape + tuple(a.shape[i] for i in lfree) + nfree
    return T.reshape(out, final)


def _norm_pad(eqn, read) -> Term:
    x = read(eqn.invars[0])
    pv = read(eqn.invars[1])  # scalar
    cfg = eqn.params["padding_config"]
    if any(c[2] != 0 for c in cfg):
        raise CaptureError("interior padding unsupported")
    if any(c[0] < 0 or c[1] < 0 for c in cfg):
        raise CaptureError("negative padding unsupported")
    out = x
    for d, (lo, hi, _) in enumerate(cfg):
        pieces = []
        if lo:
            sh = tuple(lo if i == d else out.shape[i]
                       for i in range(len(out.shape)))
            pieces.append(T.broadcast(pv, sh, ()))
        pieces.append(out)
        if hi:
            sh = tuple(hi if i == d else out.shape[i]
                       for i in range(len(out.shape)))
            pieces.append(T.broadcast(pv, sh, ()))
        if len(pieces) > 1:
            out = T.concat(pieces, d)
    return out


def _norm_gather(eqn, read) -> Optional[list]:
    """Match the embedding/take pattern: table (V, D) gathered on rows."""
    p = eqn.params
    dn = p["dimension_numbers"]
    tab = read(eqn.invars[0])
    idx = read(eqn.invars[1])
    ss = tuple(p["slice_sizes"])
    if (len(tab.shape) == 2 and dn.start_index_map == (0,)
            and dn.collapsed_slice_dims == (0,)
            and ss == (1, tab.shape[1])
            and idx.shape and idx.shape[-1] == 1):
        idx2 = T.reshape(idx, idx.shape[:-1])
        return [T.gather_rows(tab, idx2)]
    if (len(tab.shape) == 1 and dn.start_index_map == (0,)
            and dn.collapsed_slice_dims == (0,) and ss == (1,)
            and idx.shape and idx.shape[-1] == 1):
        t2 = T.reshape(tab, tab.shape + (1,))
        idx2 = T.reshape(idx, idx.shape[:-1])
        g = T.gather_rows(t2, idx2)
        return [T.reshape(g, g.shape[:-1])]
    return None


def _norm_collective(eqn, read) -> list:
    prim = eqn.primitive.name
    p = eqn.params
    if prim == "pvary" or prim == "pbroadcast":
        return [read(a) for a in eqn.invars]
    if prim == "axis_index":
        return [Term("axis_index", (), (("axis", p["axis_name"]),), (), "i")]
    if prim in ("psum", "psum_invariant"):
        axes = tuple(a for a in p["axes"] if isinstance(a, str))
        outs = []
        for a in eqn.invars:
            x = read(a)
            outs.append(Term("psum", (x,), (("axes", axes),), x.shape, x.dtype))
        return outs
    x = read(eqn.invars[0])
    if prim == "all_gather":
        axes = p["axis_name"]
        axes = tuple(axes) if isinstance(axes, tuple) else (axes,)
        d = p["all_gather_dimension"]
        sz = p["axis_size"]
        shape = tuple(x.shape[i] * sz if i == d else x.shape[i]
                      for i in range(len(x.shape)))
        if not p["tiled"]:
            shape = x.shape[:d] + (sz,) + x.shape[d:]
        return [Term("all_gather", (x,),
                     (("axes", axes), ("dim", d), ("tiled", p["tiled"])),
                     shape, x.dtype)]
    if prim == "reduce_scatter":
        axes = p["axis_name"]
        axes = tuple(axes) if isinstance(axes, tuple) else (axes,)
        d = p["scatter_dimension"]
        sz = p["axis_size"]
        assert p["tiled"], "only tiled reduce_scatter supported"
        shape = tuple(x.shape[i] // sz if i == d else x.shape[i]
                      for i in range(len(x.shape)))
        return [Term("reduce_scatter", (x,), (("axes", axes), ("dim", d)),
                     shape, x.dtype)]
    if prim == "all_to_all":
        ax = p["axis_name"]
        axes = tuple(ax) if isinstance(ax, tuple) else (ax,)
        sa, ca = p["split_axis"], p["concat_axis"]
        assert p.get("tiled", True), "only tiled all_to_all supported"
        ov = eqn.outvars[0].aval  # shape from outvar (depends on group size)
        return [Term("all_to_all", (x,),
                     (("axes", axes), ("split", sa), ("concat", ca)),
                     tuple(ov.shape), x.dtype)]
    if prim == "ppermute":
        ax = p["axis_name"]
        if isinstance(ax, tuple):
            assert len(ax) == 1, "multi-axis ppermute unsupported"
            ax = ax[0]
        return [Term("ppermute", (x,),
                     (("axis", ax), ("perm", tuple(map(tuple, p["perm"])))),
                     x.shape, x.dtype)]
    raise AssertionError(prim)


# ---------------------------------------------------------------------------
# SPMD expansion: per-rank instantiation + collective translation
# ---------------------------------------------------------------------------

def rank_tag(axis_names, coords) -> str:
    """Name suffix identifying one rank, e.g. ``@dp0,tp1``."""
    return "@" + ",".join(f"{a}{c}" for a, c in zip(axis_names, coords))


def expand_spmd(cap: SpmdCapture) -> tuple[Graph, dict]:
    """Expand the per-rank SPMD graph into a multi-rank Graph.

    Returns (expanded graph, input relation R_i) where R_i maps each logical
    (sequential) input name to a list of clean Terms over expanded input
    tensors — derived from the in_specs (§2.1: the distribution strategy's
    input relation; deriving it from the sharding spec is our extension).
    """
    g = cap.graph
    axis_names = tuple(cap.mesh_axes)
    sizes = tuple(cap.mesh_axes[a] for a in axis_names)
    all_coords = list(itertools.product(*[range(s) for s in sizes]))

    out = Graph([], [], [], {}, {}, {})

    def reg(name, shape, dtype):
        out.shapes[name] = shape
        out.dtypes[name] = dtype

    # per-rank inputs
    for name in g.inputs:
        for c in all_coords:
            nm = name + rank_tag(axis_names, c)
            reg(nm, g.shapes[name], g.dtypes[name])
            out.inputs.append(nm)
    # consts are rank-invariant: register once per rank (same value)
    for cname, val in g.consts.items():
        for c in all_coords:
            nm = cname + rank_tag(axis_names, c)
            out.consts[nm] = val
            reg(nm, tuple(val.shape), _dt(val.dtype))

    def group(coords, axes):
        """Rank-group of ``coords`` varying ``axes`` (ordered by coordinate)."""
        idxs = [axis_names.index(a) for a in axes]
        ranges = [range(sizes[i]) for i in idxs]
        members = []
        for combo in itertools.product(*ranges):
            c = list(coords)
            for i, v in zip(idxs, combo):
                c[i] = v
            members.append(tuple(c))
        return members

    # per-rank scalar-constant propagation: axis_index arithmetic becomes
    # literal per rank, letting dynamic slices fold to static slices.
    scalar_env: dict = {}
    for name, term in g.defs:
        for c in all_coords:
            tag = rank_tag(axis_names, c)
            inst = _instantiate(term, tag, c, axis_names, sizes, group, out,
                                scalar_env)
            nm = name + tag
            if inst.shape == ():
                v = _fold_scalar(inst)
                if v is not None:
                    scalar_env[nm] = v
                    inst = T.lit(v)
            reg(nm, inst.shape, inst.dtype)
            out.defs.append((nm, inst))

    for name in g.outputs:
        for c in all_coords:
            out.outputs.append(name + rank_tag(axis_names, c))

    r_i = derive_input_relation(g, cap.in_specs, axis_names, sizes, all_coords)
    return out, r_i


def _instantiate(term: Term, tag: str, coords, axis_names, sizes, group,
                 out_graph, scalar_env=None) -> Term:
    """Instantiate a per-rank term for a specific rank coordinate."""
    scalar_env = scalar_env or {}

    def go(t: Term) -> Term:
        if t.op == "tensor":
            nm = t.name + tag
            if nm in scalar_env:
                return T.lit(scalar_env[nm])
            return T.tensor(nm, t.shape, t.dtype)
        if t.op == "lit":
            return t
        if t.op == "axis_index":
            return T.lit(coords[axis_names.index(t.attr("axis"))])
        if t.op == "psum":
            members = group(coords, t.attr("axes"))
            return T.add_n(_retag(t.args[0], rank_tag(axis_names, m), m,
                                  axis_names, sizes, group)
                           for m in members)
        if t.op == "all_gather":
            gmembers = group(coords, t.attr("axes"))
            d, tiled = t.attr("dim"), t.attr("tiled")
            pieces = [_retag(t.args[0], rank_tag(axis_names, m), m,
                             axis_names, sizes, group) for m in gmembers]
            if tiled:
                return T.concat(pieces, d)
            pieces = [T.reshape(p, p.shape[:d] + (1,) + p.shape[d:])
                      for p in pieces]
            return T.concat(pieces, d) if len(pieces) > 1 else pieces[0]
        if t.op == "reduce_scatter":
            gmembers = group(coords, t.attr("axes"))
            d = t.attr("dim")
            pieces = [_retag(t.args[0], rank_tag(axis_names, m), m,
                             axis_names, sizes, group) for m in gmembers]
            s = T.add_n(pieces)
            k = gmembers.index(coords)
            blk = s.shape[d] // len(gmembers)
            starts = tuple(k * blk if i == d else 0 for i in range(len(s.shape)))
            limits = tuple((k + 1) * blk if i == d else s.shape[i]
                           for i in range(len(s.shape)))
            return T.slice_(s, starts, limits)
        if t.op == "all_to_all":
            gmembers = group(coords, t.attr("axes"))
            sa, ca = t.attr("split"), t.attr("concat")
            n = len(gmembers)
            k = gmembers.index(coords)
            pieces = []
            for m in gmembers:
                x = _retag(t.args[0], rank_tag(axis_names, m), m,
                           axis_names, sizes, group)
                blk = x.shape[sa] // n
                starts = tuple(k * blk if i == sa else 0
                               for i in range(len(x.shape)))
                limits = tuple((k + 1) * blk if i == sa else x.shape[i]
                               for i in range(len(x.shape)))
                pieces.append(T.slice_(x, starts, limits))
            return T.concat(pieces, ca)
        if t.op == "ppermute":
            perm = dict(t.attr("perm"))
            axis = t.attr("axis")
            ai = axis_names.index(axis)
            me = coords[ai]
            src = next((s for s, dst in perm.items() if dst == me), None)
            if src is None:
                return T.broadcast(T.lit(0.0 if t.dtype == "f" else 0),
                                   t.shape, ())
            sc = tuple(src if i == ai else coords[i]
                       for i in range(len(coords)))
            return _retag(t.args[0], rank_tag(axis_names, sc), sc,
                          axis_names, sizes, group)
        args = tuple(go(a) for a in t.args)
        if t.op in ("dyn_slice", "dyn_update_slice"):
            return _fold_dynamic(t, args)
        if t.op == "select":
            # rank-conditional writes (``jnp.where(axis_index(a) == k, ...)``)
            # fold per rank once axis_index is a literal: chase the predicate
            # through its broadcast and take the branch it selects
            pred = args[0]
            while pred.op == "broadcast":
                pred = pred.args[0]
            v = _fold_scalar(pred)
            if v is not None:
                return args[1] if v else args[2]
        return Term(t.op, args, t.attrs, t.shape, t.dtype)

    return go(term)


def _retag(term: Term, tag: str, coords, axis_names, sizes, group) -> Term:
    return _instantiate(term, tag, coords, axis_names, sizes, group, None)


def _fold_dynamic(t: Term, args) -> Term:
    """Fold dynamic slices whose start indices are now literal."""
    if t.op == "dyn_slice":
        x, starts = args[0], args[1:]
        vals = _fold_scalars(starts)
        if vals is None:
            return Term(t.op, args, t.attrs, t.shape, t.dtype)
        sizes = t.attr("sizes")
        st = tuple(min(max(v, 0), d - z)
                   for v, d, z in zip(vals, x.shape, sizes))
        return T.slice_(x, st, tuple(s + z for s, z in zip(st, sizes)))
    x, u, starts = args[0], args[1], args[2:]
    vals = _fold_scalars(starts)
    if vals is None:
        return Term(t.op, args, t.attrs, t.shape, t.dtype)
    st = tuple(min(max(v, 0), d - z)
               for v, d, z in zip(vals, x.shape, u.shape))
    return T.dus(x, u, st)


def _fold_scalars(ts) -> Optional[tuple]:
    out = []
    for t in ts:
        v = _fold_scalar(t)
        if v is None:
            return None
        out.append(int(v))
    return tuple(out)


def _fold_scalar(t: Term):
    """Constant-fold a scalar term (post axis_index substitution)."""
    if t.op == "lit":
        return t.value
    if t.shape != ():
        return None
    try:
        if any(l.op == "tensor" for l in t.leaves()):
            return None
        return T.eval_term(t, {}).item()
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Input relation derivation (from PartitionSpecs)
# ---------------------------------------------------------------------------

def derive_input_relation(g: Graph, in_specs, axis_names, sizes, all_coords):
    """R_i: logical input name -> [clean Terms over per-rank input names].

    A dim sharded over mesh axes (a, b, ...) splits major-to-minor; the
    global tensor is the nested concat of per-rank pieces. Unsharded mesh
    axes replicate: each replica yields its own mapping (paper: a relation
    may contain several mappings for one tensor)."""
    r_i: dict = {}
    for name, spec in zip(g.inputs, in_specs):
        local = tuple(g.shapes[name])  # inner-jaxpr shapes are per-shard
        dt = g.dtypes[name]
        spec = tuple(spec) if spec is not None else ()
        spec = spec + (None,) * (len(local) - len(spec))
        used = []
        for entry in spec:
            if entry is None:
                continue
            entries = entry if isinstance(entry, tuple) else (entry,)
            used.extend(entries)
        unused = [a for a in axis_names if a not in used]

        def build(rep_coords: dict) -> Term:
            """Nested concat over sharded axes for one replica assignment."""
            def rec(d: int, fixed: dict) -> Term:
                if d == len(spec):
                    coords = tuple(fixed.get(a, rep_coords.get(a, 0))
                                   for a in axis_names)
                    return T.tensor(name + rank_tag(axis_names, coords),
                                    local, dt)
                entry = spec[d]
                if entry is None:
                    return rec(d + 1, fixed)
                entries = entry if isinstance(entry, tuple) else (entry,)
                def split(ei: int, fixed2: dict) -> Term:
                    if ei == len(entries):
                        return rec(d + 1, fixed2)
                    a = entries[ei]
                    n = sizes[axis_names.index(a)]
                    return T.concat([split(ei + 1, {**fixed2, a: k})
                                     for k in range(n)], d)
                return split(0, fixed)
            return rec(0, {})

        maps = []
        if unused:
            for combo in itertools.product(*[range(sizes[axis_names.index(a)])
                                             for a in unused]):
                maps.append(build(dict(zip(unused, combo))))
        else:
            maps.append(build({}))
        r_i[name] = maps
    return r_i
