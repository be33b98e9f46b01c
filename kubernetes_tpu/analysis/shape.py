"""Symbolic shape / dtype / shard interpreter for the kernel surface
(rules: ``shape``, ``dtype``, ``shard``).

The term-factored algebra spans four dispatch paths whose correctness is
a NAMED-axis discipline — ``[P, N]`` speculation, ``[T, N]`` term counts,
``[C, N, d_cap]`` readbacks, ``[S, N]`` resident keys — but at trace time
jax only sees the concrete sizes, and rank-1 broadcasting silently
absorbs a ``[P, N]`` tensor where a ``[T, N]`` one was meant whenever the
bucketed sizes happen to coincide.  This module is an abstract
interpreter over SYMBOLIC shapes: every ``jax.jit`` root declares its
parameter axes with a ``# ktpu: axes(...)`` annotation (dataclass params
resolve through the ``_KTPU_AXES`` tables next to their definitions),
and the interpreter propagates named dims through broadcasting, einsum /
dot_general contraction, reshape / concatenation, advanced indexing,
``lax.scan`` / ``while_loop`` carries and ``dynamic_update_slice``.

Annotation grammar (comment lines immediately above the root's
decorators; ``axes`` lines stack and merge):

    # ktpu: axes(sig_ids=i32[P], sig_req=i64[S,R], dc=DeviceCluster)
    # ktpu: accum(i64, i32, bool)      — dtypes allowed in loop carries
    # ktpu: static(v_cap=16)           — representative static-arg values
    #                                     for the eval_shape cross-check
    # ktpu: noinstantiate — <reason>   — root excluded from the runtime
    #                                     cross-check (shapecheck.py)

Findings:

  * ``shape`` — a root without an axes annotation; an axes name that
    matches no parameter; two DIFFERENT named dims aligned in one
    broadcast axis; vmapped operands whose mapped axes carry different
    names; einsum/dot_general contracting mismatched names; scan /
    while_loop carries whose named shape drifts between init and step.
  * ``dtype`` — true division on integer/bool operands (silent float
    promotion in integer-score kernels); arithmetic on a bool operand
    without an ``astype`` (silent bool→int promotion); a float literal
    widening an integer array (weak-type promotion inside the kernel —
    the in-kernel complement of the ``retrace`` literal rule); a loop
    carry whose dtype leaves the root's declared ``accum(...)`` set.
  * ``shard`` — with ``parallel/mesh.py``'s ``('pods', 'nodes')`` mesh
    sharding the N axis, every op is classified N-axis-preserving
    (elementwise / other-axis reductions: fine), N-axis-REDUCING
    (reductions, einsum contractions and segment ops over N — each must
    live under a helper declared in its module's ``_KTPU_N_COLLECTIVES``
    roster, the static inventory of cross-shard collectives the
    multichip refactor must route through jax collectives), or
    implicitly N-axis-GATHERING (advanced indexing / scatter with a
    traced index into an N axis — flagged the same way).

The interpreter is deliberately PERMISSIVE: anything it cannot model
evaluates to Unknown and Unknown never produces a finding — only
confidently-known named mismatches fire.  The runtime complement
(``analysis/shapecheck.py``, KTPU_SANITIZE=1) cross-validates the
inferred root shapes against ``jax.eval_shape`` so the interpreter
itself cannot silently rot as the kernels evolve.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from kubernetes_tpu.analysis.core import (
    RULE_DTYPE,
    RULE_SHAPE,
    RULE_SHARD,
    Checker,
    SourceModule,
    dotted_name,
    module_literal,
)
from kubernetes_tpu.analysis.jit import _jit_decoration

# the mesh axis this analysis audits (parallel/mesh.py: ('pods', 'nodes')
# with node-major snapshot tensors partitioned over 'nodes', i.e. dim N)
NODE_AXIS = "N"

# A roster entry is RESOLVED once its reason leads with an explicit
# sharding story: ``resolved(<mechanism>): <how>`` where mechanism is
#   collective — GSPMD inserts the cross-shard psum/all-gather/all-to-all
#   local      — the op addresses only the owning shard's rows (rank-1
#                commits, fork-axis parallelism)
#   replicated — the crossed operand replicates on the mesh, so the
#                "crossing" is shard-local by layout
# Unresolved entries are findings: the multichip worklist is a BURN-DOWN
# (MULTICHIP.md inventory), not a parking lot.
RESOLVED_ROSTER_RE = re.compile(
    r"^resolved\((collective|local|replicated)\):\s+\S"
)

_ANNOT_RE = re.compile(
    r"#\s*ktpu:\s*(axes|static|accum|noinstantiate)\b\s*(.*)$"
)

_DTYPES = {
    "bool": "bool",
    "i8": "i8",
    "i16": "i16",
    "i32": "i32",
    "i64": "i64",
    "u8": "u8",
    "u16": "u16",
    "u32": "u32",
    "u64": "u64",
    "f16": "f16",
    "f32": "f32",
    "f64": "f64",
}
_JNP_DTYPE_ATTRS = {
    "int8": "i8",
    "int16": "i16",
    "int32": "i32",
    "int64": "i64",
    "uint8": "u8",
    "uint16": "u16",
    "uint32": "u32",
    "uint64": "u64",
    "bool_": "bool",
    "float16": "f16",
    "float32": "f32",
    "float64": "f64",
    "bfloat16": "f16",
}
_INT_DTYPES = {"i8", "i16", "i32", "i64", "u8", "u16", "u32", "u64"}
_FLOAT_DTYPES = {"f16", "f32", "f64"}
_WIDTH = {"bool": 0, "i8": 1, "u8": 1, "i16": 2, "u16": 2, "i32": 3,
          "u32": 3, "i64": 4, "u64": 4, "f16": 5, "f32": 6, "f64": 7}

_REDUCERS = {
    "sum", "max", "min", "all", "any", "prod", "mean", "argmax", "argmin",
    "count_nonzero", "nanmax", "nanmin", "nansum",
}
_SAME_SHAPE_FNS = {
    "abs", "sign", "negative", "logical_not", "invert", "exp", "log",
    "sqrt", "flip", "sort", "argsort", "cumsum", "cummax", "cumprod",
    "cumulative_sum", "round", "floor", "ceil", "bitwise_not",
}
_BROADCAST_FNS = {
    "where", "minimum", "maximum", "add", "subtract", "multiply",
    "logical_and", "logical_or", "logical_xor", "equal", "not_equal",
    "greater", "greater_equal", "less", "less_equal", "clip", "mod",
    "floor_divide", "power", "bitwise_and", "bitwise_or",
}
_BOOL_RESULT_FNS = {
    "logical_and", "logical_or", "logical_xor", "logical_not", "equal",
    "not_equal", "greater", "greater_equal", "less", "less_equal",
    "isin", "isnan",
}


# ---------------------------------------------------------------------------
# symbolic dims: canonical linear combinations over named symbols.
# A dim is an int, a "lin" tuple (const, ((sym, coeff), ...)) or None
# (unknown).  Non-linear combinations collapse to a single OPAQUE symbol
# whose name is the canonical rendering — deterministic, so two
# occurrences of the same computation stay equal.
# ---------------------------------------------------------------------------


def dim_of_sym(sym: str):
    return (0, ((sym, 1),))


def _as_lin(d):
    if d is None:
        return None
    if isinstance(d, int):
        return (d, ())
    return d


def dim_add(a, b, sign: int = 1):
    a, b = _as_lin(a), _as_lin(b)
    if a is None or b is None:
        return None
    syms: Dict[str, int] = dict(a[1])
    for s, c in b[1]:
        syms[s] = syms.get(s, 0) + sign * c
    items = tuple(sorted((s, c) for s, c in syms.items() if c != 0))
    const = a[0] + sign * b[0]
    if not items:
        return const
    return (const, items)


def dim_mul(a, b):
    a, b = _as_lin(a), _as_lin(b)
    if a is None or b is None:
        return None
    if not a[1] and not b[1]:
        return a[0] * b[0]
    if not a[1]:
        if a[0] == 0:
            return 0
        syms = tuple((s, c * a[0]) for s, c in b[1])
        return (b[0] * a[0], syms)
    if not b[1]:
        return dim_mul(b, a)
    x, y = sorted((dim_str(a), dim_str(b)))
    return dim_of_sym(f"({x}*{y})")


def dim_opaque(op: str, *parts):
    rendered = []
    for p in parts:
        p = _as_lin(p)
        if p is None:
            return None
        rendered.append(dim_str(p))
    return dim_of_sym(f"{op}({','.join(rendered)})")


def dim_str(d) -> str:
    d = _as_lin(d)
    if d is None:
        return "?"
    const, syms = d
    parts = []
    for s, c in syms:
        parts.append(s if c == 1 else f"{c}*{s}")
    if const or not parts:
        parts.append(str(const))
    return "+".join(parts).replace("+-", "-")


def dim_eq(a, b) -> bool:
    a, b = _as_lin(a), _as_lin(b)
    return a is not None and b is not None and a == b


def dim_is_one(d) -> bool:
    return _as_lin(d) == (1, ())


def dim_is_named(d) -> bool:
    d = _as_lin(d)
    return d is not None and bool(d[1])


def dim_is_node_axis(d) -> bool:
    return dim_eq(d, dim_of_sym(NODE_AXIS))


def shape_str(shape) -> str:
    if shape is None:
        return "[?]"
    return "[" + ", ".join(dim_str(d) for d in shape) + "]"


def dims_product(dims):
    out = 1
    for d in dims:
        out = dim_mul(out, d)
        if out is None:
            return None
    return out


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------

_UNSET = object()


class Unknown:
    __slots__ = ()

    def __repr__(self):
        return "Unknown"


UNKNOWN = Unknown()


class Arr:
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype=None):
        # shape: tuple of dims (each int / lin / None) or None = unknown rank
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype

    def __repr__(self):
        return f"Arr({shape_str(self.shape)}, {self.dtype})"


class TupV:
    __slots__ = ("items",)

    def __init__(self, items):
        self.items = list(items)

    def __repr__(self):
        return f"TupV({self.items})"


class DictV:
    __slots__ = ("entries",)

    def __init__(self, entries=None):
        self.entries = dict(entries or {})

    def __repr__(self):
        return f"DictV({sorted(self.entries)})"


class RecV:
    __slots__ = ("cls", "fields")

    def __init__(self, cls, fields=None):
        self.cls = cls
        self.fields = dict(fields or {})

    def __repr__(self):
        return f"RecV({self.cls})"


class CtorV:
    """A NamedTuple / dataclass class object (callable constructor)."""

    __slots__ = ("cls", "field_order")

    def __init__(self, cls, field_order):
        self.cls = cls
        self.field_order = list(field_order)


class FuncV:
    """A locally-defined function or lambda with its live closure env."""

    __slots__ = ("key", "node", "env", "base")

    def __init__(self, key, node, env, base):
        self.key = key  # engine func key, or None for lambdas
        self.node = node
        self.env = env  # LIVE reference to the defining environment
        self.base = base


class DimV:
    """A host int whose value is a symbolic dim (usually from .shape[i])."""

    __slots__ = ("lin",)

    def __init__(self, lin):
        self.lin = _as_lin(lin) if not (lin is None or isinstance(lin, tuple)) else lin

    def __repr__(self):
        return f"DimV({dim_str(self.lin)})"


class StaticV:
    """A host static value (trace-time constant).  ``value`` is the
    concrete Python value when known, else UNSET."""

    __slots__ = ("value",)

    def __init__(self, value=_UNSET):
        self.value = value

    def __repr__(self):
        return "StaticV" if self.value is _UNSET else f"StaticV({self.value!r})"


class DtypeV:
    __slots__ = ("dt",)

    def __init__(self, dt):
        self.dt = dt


class ModV:
    __slots__ = ("base",)

    def __init__(self, base):
        self.base = base


NONE = StaticV(None)


def is_none_val(v) -> bool:
    return isinstance(v, StaticV) and v.value is None


def definitely_not_none(v) -> bool:
    return isinstance(v, (Arr, TupV, DictV, RecV, FuncV, DimV, CtorV)) or (
        isinstance(v, StaticV) and v.value is not _UNSET and v.value is not None
    )


def join(a, b):
    """Pointwise join of two abstract values (if/else merge, loop carry)."""
    if a is b:
        return a
    if isinstance(a, Unknown) or isinstance(b, Unknown):
        return UNKNOWN
    if isinstance(a, Arr) and isinstance(b, Arr):
        if a.shape is None or b.shape is None or len(a.shape) != len(b.shape):
            shape = None
        else:
            shape = tuple(
                da if dim_eq(da, db_) else None
                for da, db_ in zip(a.shape, b.shape)
            )
        return Arr(shape, a.dtype if a.dtype == b.dtype else None)
    if isinstance(a, TupV) and isinstance(b, TupV) and len(a.items) == len(b.items):
        return TupV([join(x, y) for x, y in zip(a.items, b.items)])
    if isinstance(a, DictV) and isinstance(b, DictV):
        out = {}
        for k in set(a.entries) | set(b.entries):
            if k in a.entries and k in b.entries:
                out[k] = join(a.entries[k], b.entries[k])
            else:
                out[k] = a.entries.get(k, b.entries.get(k))
        return DictV(out)
    if isinstance(a, RecV) and isinstance(b, RecV) and a.cls == b.cls:
        out = {}
        for k in set(a.fields) | set(b.fields):
            if k in a.fields and k in b.fields:
                out[k] = join(a.fields[k], b.fields[k])
            else:
                out[k] = a.fields.get(k, b.fields.get(k))
        return RecV(a.cls, out)
    if isinstance(a, DimV) and isinstance(b, DimV):
        return a if dim_eq(a.lin, b.lin) else DimV(None)
    if isinstance(a, StaticV) and isinstance(b, StaticV):
        if a.value is not _UNSET and b.value is not _UNSET and a.value == b.value:
            return a
        return StaticV()
    if isinstance(a, FuncV) and isinstance(b, FuncV) and a.node is b.node:
        return a
    return UNKNOWN


def promote_dtype(a: Optional[str], b: Optional[str]) -> Optional[str]:
    if a is None or b is None:
        return None
    if a == b:
        return a
    wa, wb = _WIDTH.get(a), _WIDTH.get(b)
    if wa is None or wb is None:
        return None
    return a if wa >= wb else b


# ---------------------------------------------------------------------------
# annotation parsing
# ---------------------------------------------------------------------------


class RootAnnotation:
    __slots__ = ("axes", "accum", "static_values", "noinstantiate", "line",
                 "has_axes", "ret")

    def __init__(self):
        self.axes: Dict[str, ast.expr] = {}
        self.ret: Optional[ast.expr] = None
        self.accum: Optional[Set[str]] = None
        self.static_values: Dict[str, object] = {}
        self.noinstantiate: Optional[str] = None
        self.has_axes = False
        self.line = 0


def _split_arrow(payload: str) -> Tuple[str, Optional[str]]:
    depth = 0
    for i in range(len(payload) - 1):
        ch = payload[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif depth == 0 and payload[i : i + 2] == "->":
            return payload[:i].rstrip(), payload[i + 2 :].strip()
    return payload.rstrip(), None


def parse_annotations(mod: SourceModule, first_line: int) -> RootAnnotation:
    """Collect the ``# ktpu:`` annotation block of comment lines
    immediately above ``first_line`` (the def or its first decorator)."""
    ann = RootAnnotation()
    i = first_line - 1  # line above, 1-based
    block: List[Tuple[int, str, str]] = []
    while i >= 1:
        raw = mod.lines[i - 1].strip()
        if not raw.startswith("#"):
            break
        m = _ANNOT_RE.search(raw)
        if m:
            block.append((i, m.group(1), m.group(2).strip()))
        i -= 1
    for line, kind, payload in reversed(block):
        ann.line = ann.line or line
        if kind == "noinstantiate":
            ann.noinstantiate = payload.lstrip("—-– :").strip() or "unspecified"
            continue
        body, arrow = _split_arrow(payload)
        try:
            call = ast.parse(f"__a__{body}", mode="eval").body
        except SyntaxError:
            continue
        if not isinstance(call, ast.Call):
            continue
        if kind == "axes":
            ann.has_axes = True
            for kw in call.keywords:
                if kw.arg is not None:
                    ann.axes[kw.arg] = kw.value
            if arrow:
                try:
                    ann.ret = ast.parse(arrow, mode="eval").body
                except SyntaxError:
                    pass
        elif kind == "accum":
            ann.accum = set()
            for a in call.args:
                if isinstance(a, ast.Name):
                    ann.accum.add(_DTYPES.get(a.id, a.id))
        elif kind == "static":
            for kw in call.keywords:
                if kw.arg is None:
                    continue
                try:
                    ann.static_values[kw.arg] = ast.literal_eval(kw.value)
                except (ValueError, SyntaxError):
                    pass
    return ann


def spec_to_aval(expr: ast.expr, class_tables: Dict[str, Dict[str, str]],
                 ns: str = ""):
    """An annotation spec expression → abstract value.

    ``i64[S,R]`` → Arr; bare dtype → scalar Arr; ``DeviceCluster`` (a
    ``_KTPU_AXES`` class) → RecV from its table; ``DTable[M,1]`` → the
    class with its ``*`` lead dims bound; ``any`` → Unknown.  ``ns``
    namespaces the class schema's own symbols (two DTables bucketed
    independently must not unify their per-table widths).
    """
    if isinstance(expr, ast.Name):
        if expr.id == "any" or expr.id == "key":
            return UNKNOWN
        if expr.id in _DTYPES:
            return Arr((), _DTYPES[expr.id])
        if expr.id in class_tables:
            return _class_to_rec(expr.id, (), class_tables, ns or expr.id)
        return UNKNOWN
    if isinstance(expr, ast.Tuple):
        return TupV([spec_to_aval(e, class_tables, ns) for e in expr.elts])
    if isinstance(expr, ast.Subscript):
        base = expr.value
        dims_expr = expr.slice
        dims = _spec_dims(dims_expr, ns)
        if isinstance(base, ast.Name):
            if base.id in _DTYPES:
                return Arr(dims, _DTYPES[base.id])
            if base.id in class_tables:
                return _class_to_rec(base.id, dims, class_tables, ns or base.id)
    return UNKNOWN


def _spec_dims(expr: ast.expr, ns: str):
    elts = expr.elts if isinstance(expr, ast.Tuple) else [expr]
    dims = []
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, int):
            dims.append(e.value)
        elif isinstance(e, ast.Name):
            if e.id == "_":
                dims.append(None)
            else:
                dims.append(dim_of_sym(e.id))
        else:
            dims.append(None)
    return tuple(dims)


def _class_to_rec(cls: str, lead, class_tables, ns: str):
    table = class_tables.get(cls, {})
    fields = {}
    for fname, spec in table.items():
        fields[fname] = _field_spec_to_aval(
            spec, lead, class_tables, ns, fname
        )
    return RecV(cls, fields)


def _field_spec_to_aval(spec: str, lead, class_tables, ns: str,
                        fname: str = ""):
    """A ``_KTPU_AXES`` field spec string → abstract value.  ``*`` in a
    dims position splices the owner's lead dims; symbols spelled with a
    trailing underscore (``Q_``) are PRIVATE to the class schema and get
    namespaced by the owning field path — two independently-bucketed
    DTables must not unify their per-table widths."""
    try:
        expr = ast.parse(spec.strip().replace("*", "_star_"), mode="eval").body
    except SyntaxError:
        return UNKNOWN
    if isinstance(expr, ast.Subscript):
        base = expr.value
        raw = expr.slice
        elts = raw.elts if isinstance(raw, ast.Tuple) else [raw]
        dims: List[object] = []
        for e in elts:
            if isinstance(e, ast.Name) and e.id == "_star_":
                dims.extend(lead)
            elif isinstance(e, ast.Constant) and isinstance(e.value, int):
                dims.append(e.value)
            elif isinstance(e, ast.Name):
                if e.id.endswith("_"):
                    dims.append(dim_of_sym(f"{ns}.{e.id[:-1]}"))
                else:
                    dims.append(dim_of_sym(e.id))
            else:
                dims.append(None)
        if isinstance(base, ast.Name):
            if base.id in _DTYPES:
                return Arr(tuple(dims), _DTYPES[base.id])
            if base.id in class_tables:
                return _class_to_rec(
                    base.id, tuple(dims), class_tables,
                    f"{ns}.{fname}" if fname else ns,
                )
    if isinstance(expr, ast.Name):
        if expr.id in _DTYPES:
            return Arr((), _DTYPES[expr.id])
        if expr.id in class_tables:
            return _class_to_rec(
                expr.id, (), class_tables, f"{ns}.{fname}" if fname else ns
            )
    return UNKNOWN


# ---------------------------------------------------------------------------
# module indexing
# ---------------------------------------------------------------------------


class _FuncRec:
    __slots__ = ("key", "mod", "node", "qual", "base", "enclosing")

    def __init__(self, key, mod, node, qual, base, enclosing):
        self.key = key
        self.mod = mod
        self.node = node
        self.qual = qual
        self.base = base
        self.enclosing = enclosing


class _ModIndex:
    def __init__(self, mod: SourceModule, base: str):
        self.mod = mod
        self.base = base
        self.funcs: Dict[str, _FuncRec] = {}  # qual -> rec
        self.classes: Dict[str, List[str]] = {}  # NamedTuple fields
        self.dtype_aliases: Dict[str, str] = {}
        self.constants: Dict[str, object] = {}
        self.imports: Dict[str, Tuple[str, Optional[str]]] = {}
        # local name -> ('jnp'|'np'|'jax'|'lax', None) or (module_base, sym)
        self.roster: Dict[str, str] = {}
        self.roster_lines: Dict[str, int] = {}  # qual -> dict-key lineno
        self.axes_table: Dict[str, Dict[str, str]] = {}


class ShapeEngine:
    """One pass over the target modules; findings accumulate as raw
    (rule, mod, line, message) tuples — the checkers apply suppressions."""

    MAX_DEPTH = 24

    def __init__(self) -> None:
        self.mods: Dict[str, _ModIndex] = {}  # base -> index
        self.raw_findings: List[Tuple[str, SourceModule, int, str]] = []
        self._emitted: Set[Tuple[str, str, int, str]] = set()
        self.roots: List[Tuple[_FuncRec, RootAnnotation]] = []
        self.class_tables: Dict[str, Dict[str, str]] = {}
        self.summaries: Dict[tuple, object] = {}
        self._stack: List[str] = []  # active func keys (roster coverage)
        self._accum: List[Optional[Set[str]]] = []
        self.root_returns: Dict[str, object] = {}  # "base.qual" -> aval

    # -- indexing ----------------------------------------------------------

    def run(self, mods: Sequence[SourceModule]) -> "ShapeEngine":
        for mod in mods:
            self._index(mod)
        for mi in self.mods.values():
            for qual, reason in sorted(mi.roster.items()):
                if not RESOLVED_ROSTER_RE.match(reason):
                    self.emit(
                        RULE_SHARD,
                        mi.mod,
                        mi.roster_lines.get(qual, 1),
                        f"_KTPU_N_COLLECTIVES entry {qual!r} has no "
                        "resolved sharding story — prefix the reason with "
                        "'resolved(collective|local|replicated): <how>' "
                        "once the site has an explicit cross-shard "
                        "treatment (MULTICHIP.md inventory)",
                    )
        for mi in self.mods.values():
            self.class_tables.update(mi.axes_table)
        for mi in self.mods.values():
            for qual, rec in sorted(mi.funcs.items()):
                jd = _jit_decoration(rec.node)
                if jd is None:
                    continue
                first = min(
                    [d.lineno for d in rec.node.decorator_list]
                    + [rec.node.lineno]
                )
                ann = parse_annotations(rec.mod, first)
                if not ann.has_axes:
                    self.emit(
                        RULE_SHAPE,
                        rec.mod,
                        rec.node.lineno,
                        f"{qual}: jit root without a `# ktpu: axes(...)` "
                        "annotation — declare the named dims of every "
                        "array parameter",
                    )
                    continue
                self.roots.append((rec, ann))
        for rec, ann in self.roots:
            self._analyze_root(rec, ann)
        return self

    def _index(self, mod: SourceModule) -> None:
        base = mod.path.rsplit("/", 1)[-1].rsplit(".", 1)[0]
        # two target files sharing a basename must BOTH be analyzed:
        # disambiguate the index key (cross-module import resolution into
        # the shadowed one simply won't resolve — permissive, never a
        # silently-dropped file)
        n = 2
        while base in self.mods:
            base = f"{base}#{n}"
            n += 1
        mi = _ModIndex(mod, base)
        self.mods[mi.base] = mi
        roster = module_literal(mod.tree, "_KTPU_N_COLLECTIVES")
        if isinstance(roster, dict):
            mi.roster = {str(k): str(v) for k, v in roster.items()}
            # per-entry line numbers: the burn-down findings (and their
            # suppressions) anchor to the entry's own dict-key line
            for node in mod.tree.body:
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "_KTPU_N_COLLECTIVES"
                    and isinstance(node.value, ast.Dict)
                ):
                    for k in node.value.keys:
                        if isinstance(k, ast.Constant):
                            mi.roster_lines[str(k.value)] = k.lineno
        axes = module_literal(mod.tree, "_KTPU_AXES")
        if isinstance(axes, dict):
            mi.axes_table = {
                str(c): {str(f): str(s) for f, s in t.items()}
                for c, t in axes.items()
                if isinstance(t, dict)
            }

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    if a.name == "jax.numpy":
                        mi.imports[a.asname or "jnp"] = ("jnp", None)
                    elif a.name == "numpy":
                        mi.imports[local] = ("np", None)
                    elif a.name == "jax":
                        mi.imports[local] = ("jax", None)
            elif isinstance(node, ast.ImportFrom):
                m = node.module or ""
                for a in node.names:
                    local = a.asname or a.name
                    if m == "jax" and a.name == "numpy":
                        mi.imports[local] = ("jnp", None)
                    elif m == "jax" and a.name == "lax":
                        mi.imports[local] = ("lax", None)
                    elif m == "jax":
                        mi.imports[local] = ("jax", None)
                    elif m == "numpy":
                        mi.imports[local] = ("np", None)
                    elif m.startswith("kubernetes_tpu"):
                        tail = m.rsplit(".", 1)[-1]
                        if a.name[:1].islower() and m.count(".") <= 1:
                            mi.imports[local] = ("@mod", a.name)
                        else:
                            mi.imports[local] = (tail, a.name)
                    else:
                        mi.imports[local] = ("@ext", a.name)

        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                dn = dotted_name(node.value)
                if dn is not None:
                    leaf = dn.split(".")[-1]
                    if leaf in _JNP_DTYPE_ATTRS:
                        mi.dtype_aliases[name] = _JNP_DTYPE_ATTRS[leaf]
                        continue
                try:
                    mi.constants[name] = ast.literal_eval(node.value)
                except (ValueError, SyntaxError):
                    pass
            elif isinstance(node, ast.ClassDef):
                bases = [dotted_name(b) for b in node.bases]
                fields = [
                    st.target.id
                    for st in node.body
                    if isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)
                ]
                if any(b and b.split(".")[-1] == "NamedTuple" for b in bases) \
                        or any(
                            dotted_name(d) and dotted_name(d).split(".")[-1]
                            == "dataclass"
                            or (isinstance(d, ast.Call) and dotted_name(d.func))
                            for d in node.decorator_list
                        ) or fields:
                    mi.classes[node.name] = fields

        def walk_defs(body, qual, rec):
            for sub in body:
                if isinstance(sub, ast.FunctionDef):
                    index_fn(sub, f"{qual}.{sub.name}", rec)
                    continue
                # nested defs under if/for/with/try still get keys —
                # resident's run_tail and explain's _spread_one live
                # inside conditionals
                for attr in ("body", "orelse", "finalbody"):
                    b = getattr(sub, attr, None)
                    if b:
                        walk_defs(b, qual, rec)
                for h in getattr(sub, "handlers", ()) or ():
                    walk_defs(h.body, qual, rec)

        def index_fn(fn, qual, enclosing):
            rec = _FuncRec(f"{mi.base}:{qual}", mod, fn, qual, mi.base,
                           enclosing)
            mi.funcs[qual] = rec
            walk_defs(fn.body, qual, rec)

        for node in mod.tree.body:
            if isinstance(node, ast.FunctionDef):
                index_fn(node, node.name, None)
            elif isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, ast.FunctionDef):
                        index_fn(item, f"{node.name}.{item.name}", None)

    # -- findings ----------------------------------------------------------

    def emit(self, rule: str, mod: SourceModule, line: int, msg: str) -> None:
        key = (rule, mod.path, line, msg)
        if key in self._emitted:
            return
        self._emitted.add(key)
        self.raw_findings.append((rule, mod, line, msg))

    def _covered(self) -> bool:
        """Is the current abstract call stack under a rostered collective
        helper?  (func keys are "base:qual"; rosters are per-module.)"""
        for key in self._stack:
            base, qual = key.split(":", 1)
            mi = self.mods.get(base)
            if mi is not None and qual in mi.roster:
                return True
        return False

    def _fn_label(self) -> str:
        return self._stack[-1].split(":", 1)[1] if self._stack else "<module>"

    def _cur_mod(self) -> Optional[SourceModule]:
        if not self._stack:
            return None
        base = self._stack[-1].split(":", 1)[0]
        mi = self.mods.get(base)
        return mi.mod if mi else None

    def _shard_flag(self, node, kind: str, detail: str) -> None:
        if self._covered():
            return
        mod = self._cur_mod()
        if mod is None:
            return
        self.emit(
            RULE_SHARD,
            mod,
            node.lineno,
            f"{self._fn_label()}: {kind} over the sharded {NODE_AXIS} axis "
            f"({detail}) outside a declared collective helper — add the "
            "enclosing function to its module's _KTPU_N_COLLECTIVES roster "
            "(with a reason) or restructure to keep the op shard-local",
        )

    def _shape_flag(self, node, msg: str) -> None:
        mod = self._cur_mod()
        if mod is not None:
            self.emit(RULE_SHAPE, mod, node.lineno, f"{self._fn_label()}: {msg}")

    def _dtype_flag(self, node, msg: str) -> None:
        mod = self._cur_mod()
        if mod is not None:
            self.emit(RULE_DTYPE, mod, node.lineno, f"{self._fn_label()}: {msg}")

    # -- broadcasting ------------------------------------------------------

    def broadcast_shapes(self, shapes, node):
        """Right-aligned broadcast with named-dim mismatch detection."""
        known = [s for s in shapes if s is not None]
        if not known:
            return None
        rank = max(len(s) for s in known)
        out = []
        for i in range(1, rank + 1):
            dims = [s[-i] for s in known if len(s) >= i]
            cur = None
            conflicted = False
            for d in dims:
                if d is None or dim_is_one(d):
                    continue
                if cur is None:
                    cur = d
                elif not dim_eq(cur, d):
                    if dim_is_named(cur) and dim_is_named(d):
                        self._shape_flag(
                            node,
                            f"named-dim mismatch: axis -{i} aligns "
                            f"{dim_str(cur)} with {dim_str(d)} "
                            f"(shapes {', '.join(shape_str(s) for s in known)})"
                            " — rank-1 broadcasting would silently absorb "
                            "this when the bucketed sizes coincide",
                        )
                    cur = None
                    conflicted = True
                    break
            if cur is None and not conflicted and dims and all(
                d is not None and dim_is_one(d) for d in dims
            ):
                cur = 1
            out.append(cur)
        out.reverse()
        return tuple(out)

    # -- dims from values --------------------------------------------------

    def dim_of_value(self, v):
        """Host value → symbolic dim (for shape tuples / sizes)."""
        if isinstance(v, DimV):
            return v.lin
        if isinstance(v, StaticV) and isinstance(v.value, int) and not \
                isinstance(v.value, bool):
            return v.value
        return None

    def shape_from_value(self, v):
        """A shape argument value → dims tuple (or None)."""
        if isinstance(v, TupV):
            return tuple(self.dim_of_value(x) for x in v.items)
        d = self.dim_of_value(v)
        if d is not None:
            return (d,)
        return None

    # -- name resolution ---------------------------------------------------

    def global_av(self, base: str, name: str, depth: int = 0):
        """Module-global lookup (functions, classes, dtype aliases,
        literal constants, import aliases)."""
        mi = self.mods.get(base)
        if mi is None or depth > 4:
            return UNKNOWN
        if name in mi.dtype_aliases:
            return DtypeV(mi.dtype_aliases[name])
        if name in mi.funcs and "." not in name:
            return FuncV(mi.funcs[name].key, mi.funcs[name].node, None, base)
        if name in mi.classes:
            return CtorV(name, mi.classes[name])
        if name in self.class_tables and name in mi.axes_table:
            return CtorV(name, list(mi.axes_table[name]))
        if name in mi.constants:
            return StaticV(mi.constants[name])
        imp = mi.imports.get(name)
        if imp is not None:
            kind, sym = imp
            if kind in ("jnp", "np", "jax", "lax"):
                return ModV(kind)
            if kind == "@mod":
                return ModV(f"#{sym}") if sym in self.mods else UNKNOWN
            if kind == "@ext":
                return StaticV()
            if kind in self.mods:
                return self.global_av(kind, sym, depth + 1)
            return StaticV()
        return UNKNOWN

    # -- dtype resolution for astype()/dtype= arguments --------------------

    def dtype_from_value(self, v) -> Optional[str]:
        if isinstance(v, DtypeV):
            return v.dt
        if isinstance(v, StaticV) and isinstance(v.value, str):
            return _DTYPES.get(v.value)
        return None

    def dtype_from_expr(self, node, env, base) -> Optional[str]:
        dn = dotted_name(node)
        if dn is not None:
            leaf = dn.split(".")[-1]
            if leaf in _JNP_DTYPE_ATTRS:
                return _JNP_DTYPE_ATTRS[leaf]
            if leaf == "bool":
                return "bool"
            if leaf in ("int", "float"):
                return "i64" if leaf == "int" else "f64"
            # .dtype attribute of a known array
            if isinstance(node, ast.Attribute) and node.attr == "dtype":
                v = self.eval(node.value, env, base)
                if isinstance(v, Arr):
                    return v.dtype
            v = self.eval(node, env, base)
            return self.dtype_from_value(v)
        v = self.eval(node, env, base)
        return self.dtype_from_value(v)

    # -- expression evaluation ---------------------------------------------

    def eval(self, node, env, base):
        try:
            return self._eval(node, env, base)
        except RecursionError:
            raise
        except Exception:
            return UNKNOWN

    def _eval(self, node, env, base):
        if isinstance(node, ast.Constant):
            if node.value is None:
                return NONE
            return StaticV(node.value)
        if isinstance(node, ast.Name):
            if node.id in env:
                return env[node.id]
            return self.global_av(base, node.id)
        if isinstance(node, ast.Attribute):
            return self._eval_attr(node, env, base)
        if isinstance(node, ast.Subscript):
            return self._eval_subscript(node, env, base)
        if isinstance(node, (ast.Tuple, ast.List)):
            return TupV([self.eval(e, env, base) for e in node.elts])
        if isinstance(node, ast.Dict):
            out = {}
            for k, v in zip(node.keys, node.values):
                if k is None:
                    sub = self.eval(v, env, base)
                    if isinstance(sub, DictV):
                        out.update(sub.entries)
                    continue
                kv = self.eval(k, env, base)
                if isinstance(kv, StaticV) and isinstance(kv.value, str):
                    out[kv.value] = self.eval(v, env, base)
            return DictV(out)
        if isinstance(node, ast.BinOp):
            return self._eval_binop(node, env, base)
        if isinstance(node, ast.UnaryOp):
            return self._eval_unary(node, env, base)
        if isinstance(node, ast.BoolOp):
            vals = [self.eval(v, env, base) for v in node.values]
            known = [v for v in vals if isinstance(v, StaticV)
                     and v.value is not _UNSET]
            if len(known) == len(vals):
                if isinstance(node.op, ast.And):
                    res = True
                    for v in known:
                        res = res and v.value
                    return StaticV(res)
                res = False
                for v in known:
                    res = res or v.value
                return StaticV(res)
            arrs = [v for v in vals if isinstance(v, Arr)]
            if arrs:
                shape = self.broadcast_shapes(
                    [a.shape for a in arrs], node
                )
                return Arr(shape, arrs[0].dtype)
            return StaticV()
        if isinstance(node, ast.Compare):
            return self._eval_compare(node, env, base)
        if isinstance(node, ast.Call):
            return self._eval_call(node, env, base)
        if isinstance(node, ast.IfExp):
            t = self.truthiness(node.test, env, base)
            if t is True:
                return self.eval(node.body, env, base)
            if t is False:
                return self.eval(node.orelse, env, base)
            return join(
                self.eval(node.body, env, base),
                self.eval(node.orelse, env, base),
            )
        if isinstance(node, ast.Lambda):
            return FuncV(None, node, env, base)
        if isinstance(node, ast.Starred):
            return self.eval(node.value, env, base)
        if isinstance(node, ast.JoinedStr):
            return StaticV()
        if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
            return self._eval_comp(node, env, base)
        return UNKNOWN

    def _eval_comp(self, node, env, base):
        # a list comprehension over a STATIC iterable of known length
        # (fixed tuples) would need unrolling; approximate: element type
        # from one abstract pass, unknown length
        inner = dict(env)
        for gen in node.generators:
            self.bind_target(gen.target, UNKNOWN, inner)
        self.eval(node.elt, inner, base)
        return UNKNOWN

    def _eval_attr(self, node, env, base):
        v = self.eval(node.value, env, base)
        attr = node.attr
        if isinstance(v, Arr):
            if attr == "shape":
                if v.shape is None:
                    return UNKNOWN
                return TupV([DimV(d) for d in v.shape])
            if attr == "ndim":
                return StaticV(len(v.shape)) if v.shape is not None else StaticV()
            if attr == "dtype":
                return DtypeV(v.dtype) if v.dtype else StaticV()
            if attr == "T":
                if v.shape is None:
                    return Arr(None, v.dtype)
                return Arr(tuple(reversed(v.shape)), v.dtype)
            if attr == "at":
                return TupV([v])  # wrapped; unwrapped by .at[...].set/add
            return UNKNOWN
        if isinstance(v, RecV):
            return v.fields.get(attr, UNKNOWN)
        if isinstance(v, ModV):
            return self._module_attr(v, attr)
        if isinstance(v, DictV):
            return UNKNOWN  # method handled at call sites
        if isinstance(v, StaticV) and v.value is not _UNSET:
            try:
                return StaticV(getattr(v.value, attr))
            except Exception:
                return StaticV()
        if isinstance(v, TupV) and attr in ("items", "keys", "values"):
            return UNKNOWN
        return UNKNOWN

    def _module_attr(self, mod: ModV, attr: str):
        if mod.base.startswith("#"):
            return self.global_av(mod.base[1:], attr)
        if mod.base in ("jnp", "np"):
            if attr in _JNP_DTYPE_ATTRS:
                return DtypeV(_JNP_DTYPE_ATTRS[attr])
            return UNKNOWN  # jnp functions handled at call sites
        return UNKNOWN

    # -- subscripting ------------------------------------------------------

    def _slice_dim(self, sl: ast.Slice, length, env, base):
        """Resulting dim of a basic slice over an axis of dim ``length``
        (bounds assumed in range — this is a linter, not a prover)."""
        if sl.step is not None:
            st = self.eval(sl.step, env, base)
            if not (isinstance(st, StaticV) and st.value == 1):
                return None

        def _neg_const(d):
            lin = _as_lin(d)
            return lin is not None and not lin[1] and lin[0] < 0

        lo = 0
        if sl.lower is not None:
            lo = self.dim_of_value(self.eval(sl.lower, env, base))
            if lo is None:
                return None
        if sl.upper is None:
            if _neg_const(lo):
                return -_as_lin(lo)[0]  # x[-k:] → k
            return dim_add(length, lo, -1) if lo != 0 else length
        up = self.dim_of_value(self.eval(sl.upper, env, base))
        if up is None:
            return None
        if _neg_const(up):
            up = dim_add(length, up)  # x[:-k] → len - k
            if up is None:
                return None
        if _neg_const(lo):
            lo = dim_add(length, lo)
            if lo is None:
                return None
        return dim_add(up, lo, -1)

    def _eval_subscript(self, node, env, base):
        v = self.eval(node.value, env, base)
        sl = node.slice
        # x.at[idx] → wrapped (base, idx-node) for the .set/.add call model
        if isinstance(v, TupV) and len(v.items) == 1 and isinstance(
            node.value, ast.Attribute
        ) and node.value.attr == "at":
            return TupV([v.items[0], StaticV(("at-index", node))])
        if isinstance(v, TupV):
            iv = self.eval(sl, env, base)
            if isinstance(iv, StaticV) and isinstance(iv.value, int):
                if -len(v.items) <= iv.value < len(v.items):
                    return v.items[iv.value]
                return UNKNOWN
            if isinstance(sl, ast.Slice) and sl.step is None:
                def _bound(e):
                    if e is None:
                        return None, True
                    bv = self.eval(e, env, base)
                    if isinstance(bv, StaticV) and isinstance(bv.value, int):
                        return bv.value, True
                    return None, False
                lo, lo_ok = _bound(sl.lower)
                up, up_ok = _bound(sl.upper)
                if lo_ok and up_ok:
                    return TupV(v.items[slice(lo, up)])
            return UNKNOWN
        if isinstance(v, DictV):
            kv = self.eval(sl, env, base)
            if isinstance(kv, StaticV) and isinstance(kv.value, str):
                return v.entries.get(kv.value, UNKNOWN)
            return UNKNOWN
        if isinstance(v, StaticV):
            if v.value is _UNSET:
                return StaticV()
            kv = self.eval(sl, env, base)
            if isinstance(kv, StaticV) and kv.value is not _UNSET:
                try:
                    return StaticV(v.value[kv.value])
                except Exception:
                    return StaticV()
            return StaticV()
        if not isinstance(v, Arr):
            return UNKNOWN
        if v.shape is None:
            return Arr(None, v.dtype)

        items = list(sl.elts) if isinstance(sl, ast.Tuple) else [sl]
        # expand Ellipsis into full slices
        n_concrete = sum(
            1 for it in items
            if not (isinstance(it, ast.Constant) and it.value is Ellipsis)
            and not (isinstance(it, ast.Constant) and it.value is None)
        )
        expanded = []
        for it in items:
            if isinstance(it, ast.Constant) and it.value is Ellipsis:
                for _ in range(len(v.shape) - n_concrete):
                    expanded.append("full")
            else:
                expanded.append(it)
        out: List[object] = []
        axis = 0
        adv_shapes = []
        adv_pos = None
        gathered_axes = []
        for it in expanded:
            if it == "full":
                out.append(v.shape[axis] if axis < len(v.shape) else None)
                axis += 1
                continue
            if isinstance(it, ast.Constant) and it.value is None:
                out.append(1)
                continue
            if isinstance(it, ast.Slice):
                length = v.shape[axis] if axis < len(v.shape) else None
                if it.lower is None and it.upper is None and it.step is None:
                    out.append(length)
                else:
                    out.append(self._slice_dim(it, length, env, base))
                axis += 1
                continue
            iv = self.eval(it, env, base)
            if isinstance(iv, Arr):
                # advanced index: traced gather into this axis
                if axis < len(v.shape):
                    gathered_axes.append(v.shape[axis])
                if adv_pos is None:
                    adv_pos = len(out)
                    out.append("ADV")
                adv_shapes.append(iv.shape)
                axis += 1
                continue
            # static / host-int index: drops the axis
            axis += 1
        # trailing untouched axes
        while axis < len(v.shape):
            out.append(v.shape[axis])
            axis += 1
        for g in gathered_axes:
            if g is not None and dim_is_node_axis(g):
                self._shard_flag(
                    node, "implicit gather",
                    f"traced index into an {NODE_AXIS}-sized axis of "
                    f"{shape_str(v.shape)}",
                )
        if adv_pos is not None:
            bshape = self.broadcast_shapes(adv_shapes, node)
            final = []
            for o in out:
                if o == "ADV":
                    final.extend(bshape if bshape is not None else [None])
                else:
                    final.append(o)
            if bshape is None:
                return Arr(None, v.dtype)
            return Arr(tuple(final), v.dtype)
        return Arr(tuple(out), v.dtype)

    # -- operators ---------------------------------------------------------

    def _arith_dtype_checks(self, node, op, vals):
        arrs = [v for v in vals if isinstance(v, Arr)]
        if not arrs:
            return
        if isinstance(op, ast.Div):
            if all(
                a.dtype in _INT_DTYPES or a.dtype == "bool"
                for a in arrs if a.dtype is not None
            ) and any(a.dtype is not None for a in arrs) and not any(
                isinstance(v, StaticV) and isinstance(v.value, float)
                for v in vals
            ):
                self._dtype_flag(
                    node,
                    "true division on integer operands promotes to float "
                    "(the integer-score kernels are exact by construction) "
                    "— use // or an explicit astype",
                )
            return
        if isinstance(op, (ast.Add, ast.Sub, ast.Mult, ast.FloorDiv,
                           ast.Mod, ast.Pow)):
            for a in arrs:
                if a.dtype == "bool":
                    self._dtype_flag(
                        node,
                        "arithmetic on a bool operand promotes implicitly "
                        "— spell .astype(...) so the accumulator dtype is "
                        "chosen, not inherited",
                    )
                    break
            for v in vals:
                if isinstance(v, StaticV) and isinstance(v.value, float) \
                        and not isinstance(v.value, bool):
                    if any(a.dtype in _INT_DTYPES for a in arrs):
                        self._dtype_flag(
                            node,
                            "float literal widens an integer array "
                            "(weak-type promotion inside the kernel)",
                        )
                    break

    def _eval_binop(self, node, env, base):
        lv = self.eval(node.left, env, base)
        rv = self.eval(node.right, env, base)
        op = node.op
        # host-int symbolic arithmetic
        hl = isinstance(lv, (DimV, StaticV))
        hr = isinstance(rv, (DimV, StaticV))
        if hl and hr:
            if isinstance(lv, StaticV) and isinstance(rv, StaticV) and \
                    lv.value is not _UNSET and rv.value is not _UNSET:
                try:
                    return StaticV(_PYOPS[type(op)](lv.value, rv.value))
                except Exception:
                    return StaticV()
            dl = self.dim_of_value(lv)
            dr = self.dim_of_value(rv)
            if dl is not None and dr is not None:
                if isinstance(op, ast.Add):
                    return DimV(dim_add(dl, dr))
                if isinstance(op, ast.Sub):
                    return DimV(dim_add(dl, dr, -1))
                if isinstance(op, ast.Mult):
                    return DimV(dim_mul(dl, dr))
                if isinstance(op, ast.FloorDiv):
                    return DimV(dim_opaque("div", dl, dr))
                if isinstance(op, ast.Mod):
                    return DimV(dim_opaque("mod", dl, dr))
            if isinstance(lv, TupV) or isinstance(rv, TupV):
                pass
            return StaticV()
        # tuple concatenation / repetition (shape algebra)
        if isinstance(lv, TupV) and isinstance(rv, TupV) and \
                isinstance(op, ast.Add):
            return TupV(lv.items + rv.items)
        if isinstance(lv, TupV) and isinstance(op, ast.Mult):
            n = rv.value if isinstance(rv, StaticV) and isinstance(
                rv.value, int) else None
            if n is not None and 0 <= n <= 16:
                return TupV(lv.items * n)
            return UNKNOWN
        if isinstance(rv, TupV) and isinstance(op, ast.Mult):
            n = lv.value if isinstance(lv, StaticV) and isinstance(
                lv.value, int) else None
            if n is not None and 0 <= n <= 16:
                return TupV(rv.items * n)
            return UNKNOWN
        arrs = [v for v in (lv, rv) if isinstance(v, Arr)]
        if not arrs:
            return UNKNOWN
        self._arith_dtype_checks(node, op, [lv, rv])
        shape = self.broadcast_shapes(
            [a.shape for a in arrs], node
        )
        if isinstance(op, ast.Div):
            dt = "f64"
        elif isinstance(op, (ast.LShift, ast.RShift, ast.BitAnd, ast.BitOr,
                             ast.BitXor)):
            dts = [a.dtype for a in arrs]
            if all(d == "bool" for d in dts if d is not None) and any(dts):
                dt = "bool"
            else:
                dt = promote_dtype(*(dts + [dts[0]])[:2]) if len(dts) == 2 \
                    else dts[0]
                if dt == "bool":
                    dt = None
        else:
            if len(arrs) == 2:
                dt = promote_dtype(arrs[0].dtype, arrs[1].dtype)
                if dt == "bool":
                    dt = "i64"  # bool arithmetic promotes (flagged above)
            else:
                dt = arrs[0].dtype
                if dt == "bool" and isinstance(op, (ast.Add, ast.Sub,
                                                    ast.Mult)):
                    dt = "i64"
        return Arr(shape, dt)

    def _eval_unary(self, node, env, base):
        v = self.eval(node.operand, env, base)
        if isinstance(node.op, ast.Not):
            if isinstance(v, StaticV) and v.value is not _UNSET:
                return StaticV(not v.value)
            return StaticV()
        if isinstance(v, Arr):
            if isinstance(node.op, ast.USub):
                self._arith_dtype_checks(node, ast.Sub(), [v])
            return Arr(v.shape, v.dtype)
        if isinstance(v, (DimV, StaticV)):
            if isinstance(v, StaticV) and v.value is not _UNSET:
                try:
                    return StaticV(
                        -v.value if isinstance(node.op, ast.USub) else v.value
                    )
                except Exception:
                    return StaticV()
            if isinstance(v, DimV) and isinstance(node.op, ast.USub):
                return DimV(dim_mul(v.lin, -1))
            return StaticV()
        return UNKNOWN

    def _eval_compare(self, node, env, base):
        # `x is None` / `x is not None` decide when the operand is known
        if len(node.ops) == 1 and isinstance(node.ops[0], (ast.Is, ast.IsNot)):
            sides = [node.left, node.comparators[0]]
            if any(isinstance(s, ast.Constant) and s.value is None
                   for s in sides):
                other = sides[1] if isinstance(sides[0], ast.Constant) \
                    else sides[0]
                ov = self.eval(other, env, base)
                neg = isinstance(node.ops[0], ast.IsNot)
                if is_none_val(ov):
                    return StaticV(not neg)  # `x is None` → True
                if definitely_not_none(ov):
                    return StaticV(neg)  # `x is None` → False
                return StaticV()
        vals = [self.eval(node.left, env, base)] + [
            self.eval(c, env, base) for c in node.comparators
        ]
        # dtype identity checks (`rows.dtype == jnp.bool_`) decide when
        # both sides resolve — prunes per-dtype dispatch branches
        if len(vals) == 2 and all(isinstance(v, DtypeV) for v in vals) and \
                len(node.ops) == 1 and isinstance(node.ops[0],
                                                  (ast.Eq, ast.NotEq)):
            same = vals[0].dt == vals[1].dt
            if vals[0].dt is not None and vals[1].dt is not None:
                return StaticV(
                    same if isinstance(node.ops[0], ast.Eq) else not same
                )
            return StaticV()
        statics = [v for v in vals if isinstance(v, StaticV)
                   and v.value is not _UNSET]
        if len(statics) == len(vals) and len(node.ops) == 1:
            try:
                return StaticV(
                    _PYCMP[type(node.ops[0])](statics[0].value,
                                              statics[1].value)
                )
            except Exception:
                return StaticV()
        arrs = [v for v in vals if isinstance(v, Arr)]
        if arrs:
            shape = self.broadcast_shapes([a.shape for a in arrs], node)
            return Arr(shape, "bool")
        return StaticV()

    def truthiness(self, test, env, base):
        """True / False when statically decidable, else None."""
        v = self.eval(test, env, base)
        if isinstance(v, StaticV) and v.value is not _UNSET:
            try:
                return bool(v.value)
            except Exception:
                return None
        if is_none_val(v):
            return False
        return None

    # -- calls -------------------------------------------------------------

    def _eval_call(self, node, env, base):
        func = node.func
        # call-of-a-call: `jax.vmap(fn)(args)` and friends — evaluate the
        # inner call ONCE and dispatch on its value
        if isinstance(func, ast.Call):
            callee = self.eval(func, env, base)
            if isinstance(callee, _MappedV):
                return self._call_mapped(node, callee, env, base)
            if isinstance(callee, FuncV):
                return self._call_funcv(node, callee, env, base)
            if isinstance(callee, CtorV):
                return self._construct(node, callee, env, base)
            for a in node.args:
                self.eval(a, env, base)
            return UNKNOWN
        # dict(...) / tuple() / list() builtins and dict(state, k=v) copies
        if isinstance(func, ast.Name) and func.id not in env:
            r = self._builtin_call(node, func.id, env, base)
            if r is not NOT_BUILTIN:
                return r
        # method calls on abstract values
        if isinstance(func, ast.Attribute):
            r = self._method_call(node, func, env, base)
            if r is not NOT_BUILTIN:
                return r
        dn = dotted_name(func)
        if dn is not None:
            parts = dn.split(".")
            rootv = env.get(parts[0], None)
            if rootv is None:
                rootv = self.global_av(base, parts[0])
            # jnp./np./jax./lax. library calls
            if isinstance(rootv, ModV) and not rootv.base.startswith("#"):
                return self._library_call(node, rootv.base, parts[1:], env,
                                          base)
        callee = self.eval(func, env, base)
        if isinstance(callee, FuncV):
            return self._call_funcv(node, callee, env, base)
        if isinstance(callee, CtorV):
            return self._construct(node, callee, env, base)
        if isinstance(callee, DtypeV):
            return callee  # I32(x)-style casts don't occur; keep dtype
        return UNKNOWN

    def _args_kwargs(self, node, env, base):
        args = [self.eval(a, env, base) for a in node.args
                if not isinstance(a, ast.Starred)]
        kwargs = {}
        for kw in node.keywords:
            if kw.arg is None:
                v = self.eval(kw.value, env, base)
                if isinstance(v, DictV):
                    kwargs.update(v.entries)
            else:
                kwargs[kw.arg] = self.eval(kw.value, env, base)
        return args, kwargs

    def _builtin_call(self, node, name, env, base):
        args, kwargs = None, None
        if name == "len":
            if node.args:
                v = self.eval(node.args[0], env, base)
                if isinstance(v, Arr) and v.shape is not None and v.shape:
                    return DimV(v.shape[0])
                if isinstance(v, TupV):
                    return StaticV(len(v.items))
            return StaticV()
        if name in ("min", "max"):
            args = [self.eval(a, env, base) for a in node.args]
            dims = [self.dim_of_value(a) for a in args]
            if all(d is not None for d in dims) and len(dims) >= 2:
                ints = [d for d in dims if isinstance(d, int)]
                if len(ints) == len(dims):
                    return StaticV(min(ints) if name == "min" else max(ints))
                return DimV(dim_opaque(name, *dims))
            return StaticV()
        if name in ("int", "bool", "float", "str", "abs", "sorted", "sum",
                    "repr", "hash", "isinstance", "getattr", "hasattr",
                    "frozenset", "set", "enumerate", "zip", "range",
                    "reversed", "print", "id", "any", "all", "map"):
            for a in node.args:
                self.eval(a, env, base)
            return StaticV()
        if name == "tuple":
            if node.args:
                v = self.eval(node.args[0], env, base)
                if isinstance(v, TupV):
                    return v
            return TupV([]) if not node.args else StaticV()
        if name == "list":
            if node.args:
                v = self.eval(node.args[0], env, base)
                if isinstance(v, TupV):
                    return v
                return UNKNOWN
            return TupV([])
        if name == "dict":
            args, kwargs = self._args_kwargs(node, env, base)
            entries = {}
            for a in args:
                if isinstance(a, DictV):
                    entries.update(a.entries)
                else:
                    return UNKNOWN
            entries.update(kwargs)
            return DictV(entries)
        return NOT_BUILTIN

    def _method_call(self, node, func, env, base):
        attr = func.attr
        recv_node = func.value
        # x.at[idx].set(v) / .add(v)
        if attr in ("set", "add", "multiply", "min", "max") and isinstance(
            recv_node, ast.Subscript
        ):
            wrapped = self.eval(recv_node, env, base)
            if isinstance(wrapped, TupV) and len(wrapped.items) == 2 and \
                    isinstance(wrapped.items[1], StaticV) and isinstance(
                        wrapped.items[1].value, tuple) and \
                    wrapped.items[1].value[0] == "at-index":
                arr = wrapped.items[0]
                idx_node = wrapped.items[1].value[1]
                for a in node.args:
                    self.eval(a, env, base)
                if isinstance(arr, Arr) and arr.shape is not None and \
                        arr.shape and dim_is_node_axis(arr.shape[0]):
                    iv = self.eval(idx_node.slice, env, base)
                    if isinstance(iv, Arr):
                        self._shard_flag(
                            node, "scatter",
                            f".at[...].{attr} with a traced index into an "
                            f"{NODE_AXIS}-leading array "
                            f"{shape_str(arr.shape)}",
                        )
                return arr if isinstance(arr, Arr) else UNKNOWN
        recv = self.eval(recv_node, env, base)
        if isinstance(recv, Arr):
            if attr == "astype":
                dt = None
                if node.args:
                    dt = self.dtype_from_expr(node.args[0], env, base)
                return Arr(recv.shape, dt)
            if attr == "reshape":
                return self._reshape(node, recv, env, base)
            if attr in _REDUCERS:
                return self._reduce_call(node, recv, attr, env, base)
            if attr in ("copy", "block_until_ready", "clip"):
                return Arr(recv.shape, recv.dtype)
            if attr == "transpose":
                if recv.shape is not None and not node.args:
                    return Arr(tuple(reversed(recv.shape)), recv.dtype)
                return Arr(None, recv.dtype)
            return UNKNOWN
        if isinstance(recv, DictV):
            if attr == "get":
                kv = self.eval(node.args[0], env, base) if node.args else None
                default = self.eval(node.args[1], env, base) \
                    if len(node.args) > 1 else NONE
                if isinstance(kv, StaticV) and isinstance(kv.value, str):
                    return recv.entries.get(kv.value, default)
                return UNKNOWN
            if attr == "pop":
                kv = self.eval(node.args[0], env, base) if node.args else None
                if isinstance(kv, StaticV) and isinstance(kv.value, str):
                    return recv.entries.pop(kv.value, UNKNOWN)
                return UNKNOWN
            if attr == "update":
                for a in node.args:
                    av = self.eval(a, env, base)
                    if isinstance(av, DictV):
                        recv.entries.update(av.entries)
                _, kwargs = self._args_kwargs(node, env, base)
                recv.entries.update(kwargs)
                return NONE
            if attr == "values":
                vals = list(recv.entries.values())
                return TupV(vals)
            if attr == "keys":
                return TupV([StaticV(k) for k in recv.entries])
            if attr == "items":
                return TupV([
                    TupV([StaticV(k), v]) for k, v in recv.entries.items()
                ])
            if attr == "setdefault":
                return UNKNOWN
            return UNKNOWN
        if isinstance(recv, TupV):
            if attr == "append" and node.args:
                recv.items.append(self.eval(node.args[0], env, base))
                return NONE
            if attr == "extend" and node.args:
                v = self.eval(node.args[0], env, base)
                if isinstance(v, TupV):
                    recv.items.extend(v.items)
                return NONE
            return UNKNOWN
        if isinstance(recv, RecV):
            if attr == "_replace":
                _, kwargs = self._args_kwargs(node, env, base)
                fields = dict(recv.fields)
                fields.update(kwargs)
                return RecV(recv.cls, fields)
            return UNKNOWN
        if isinstance(recv, StaticV):
            for a in node.args:
                self.eval(a, env, base)
            return StaticV()
        return NOT_BUILTIN

    # -- library (jnp / lax / jax) calls -----------------------------------

    def _library_call(self, node, libroot, tail, env, base):
        if not tail:
            return UNKNOWN
        name = tail[-1]
        # jax.lax.X / jax.ops.X / jax.random.X routed by their submodule
        sub = tail[0] if len(tail) > 1 else None
        if libroot == "jax" and sub in ("numpy",):
            libroot, sub = "jnp", None
        if libroot == "lax" or (libroot == "jax" and sub == "lax"):
            return self._lax_call(node, name, env, base)
        if libroot == "jax" and sub == "ops":
            return self._segment_call(node, name, env, base)
        if libroot == "jax" and sub == "random":
            return self._random_call(node, name, env, base)
        if libroot == "jax" and sub == "tree_util":
            vals = [self.eval(a, env, base) for a in node.args]
            if name == "tree_map" and len(vals) == 2 and not node.keywords:
                return self._tree_map(node, vals[0], vals[1], base)
            return UNKNOWN
        if libroot == "jax":
            if name == "vmap":
                return self._vmap(node, env, base)
            if name == "jit":
                return UNKNOWN
            for a in node.args:
                self.eval(a, env, base)
            return UNKNOWN
        # jnp.* / np.*
        return self._jnp_call(node, name, env, base)

    def _tree_map(self, node, fn, tree, base):
        """``tree_map(fn, tree)`` over ONE tree of arrays: ``fn`` applied
        leaf by leaf, the container kept (a record stays its class)."""
        if isinstance(tree, Arr):
            return self._call_value(node, fn, [tree], {}, base)
        if isinstance(tree, TupV):
            return TupV([self._tree_map(node, fn, it, base) for it in tree.items])
        if isinstance(tree, RecV):
            return RecV(tree.cls, {
                k: self._tree_map(node, fn, it, base)
                for k, it in tree.fields.items()
            })
        return UNKNOWN

    def _keyword(self, node, name):
        for kw in node.keywords:
            if kw.arg == name:
                return kw.value
        return None

    def _dtype_kw(self, node, env, base, pos=None):
        kw = self._keyword(node, "dtype")
        if kw is not None:
            return self.dtype_from_expr(kw, env, base)
        if pos is not None and len(node.args) > pos:
            return self.dtype_from_expr(node.args[pos], env, base)
        return None

    def _reduce_axes(self, node, arr, env, base):
        """(reduced dims, kept shape) for a reduction call over ``arr``."""
        if arr.shape is None:
            return None, None
        kw = self._keyword(node, "axis")
        if kw is None and len(node.args) > 1:
            kw = node.args[1]
        keepdims = False
        kd = self._keyword(node, "keepdims")
        if kd is not None:
            v = self.eval(kd, env, base)
            keepdims = bool(isinstance(v, StaticV) and v.value is True)
        rank = len(arr.shape)
        if kw is None:
            axes = list(range(rank))
        else:
            av = self.eval(kw, env, base)
            axes = None
            if isinstance(av, StaticV) and isinstance(av.value, int):
                axes = [av.value % rank if rank else 0]
            elif isinstance(av, TupV):
                axes = []
                for it in av.items:
                    if isinstance(it, StaticV) and isinstance(it.value, int):
                        axes.append(it.value % rank if rank else 0)
                    else:
                        return None, None
            if axes is None:
                return None, None
        reduced = [arr.shape[a] for a in axes if a < rank]
        if keepdims:
            kept = tuple(
                1 if i in axes else d for i, d in enumerate(arr.shape)
            )
        else:
            kept = tuple(
                d for i, d in enumerate(arr.shape) if i not in axes
            )
        return reduced, kept

    def _reduce_call(self, node, arr, name, env, base):
        reduced, kept = self._reduce_axes(node, arr, env, base)
        if reduced is None:
            if arr.shape is not None and len(arr.shape) <= 1 and \
                    self._keyword(node, "axis") is None and \
                    len(node.args) <= 1:
                reduced, kept = list(arr.shape), ()
            else:
                # unresolvable axis argument: permissive silence
                return Arr(None, None)
        for d in reduced:
            if d is not None and dim_is_node_axis(d):
                self._shard_flag(
                    node, f"{name} reduction",
                    f"reduces {shape_str(arr.shape)} over {NODE_AXIS}",
                )
                break
        if name in ("any", "all"):
            dt = "bool"
        elif name in ("argmax", "argmin", "count_nonzero"):
            dt = None
        elif name in ("sum", "prod", "nansum") and (
            arr.dtype == "bool" or arr.dtype in _INT_DTYPES
        ):
            # numpy accumulation semantics: integer/bool sums promote to
            # the default int — i64 with x64 (enforced at package import)
            dt = "i64" if arr.dtype != "u64" else "u64"
        elif name == "mean":
            dt = None
        else:
            dt = arr.dtype
        return Arr(kept, dt)

    def _reshape(self, node, arr, env, base):
        args = [self.eval(a, env, base) for a in node.args]
        if len(args) == 1 and isinstance(args[0], TupV):
            dims = list(self.shape_from_value(args[0]) or [])
            if not dims and args[0].items == []:
                dims = []
        else:
            dims = [self.dim_of_value(a) for a in args]
        if any(
            isinstance(a, StaticV) and a.value == -1 for a in (
                args[0].items if len(args) == 1 and isinstance(args[0], TupV)
                else args
            )
        ):
            # resolve -1 deterministically from the total element count
            flat = args[0].items if len(args) == 1 and isinstance(
                args[0], TupV) else args
            total = dims_product(arr.shape) if arr.shape is not None else None
            known = []
            neg_at = None
            for i, a in enumerate(flat):
                d = self.dim_of_value(a)
                if isinstance(a, StaticV) and a.value == -1:
                    neg_at = i
                    known.append(None)
                else:
                    known.append(d)
            if total is not None and neg_at is not None and all(
                d is not None for i, d in enumerate(known) if i != neg_at
            ):
                rest = dims_product(
                    [d for i, d in enumerate(known) if i != neg_at] or [1]
                )
                if rest is not None:
                    if dim_eq(rest, 1):
                        known[neg_at] = total
                    elif dim_eq(total, rest):
                        known[neg_at] = 1
                    else:
                        known[neg_at] = dim_opaque("div", total, rest)
            return Arr(tuple(known), arr.dtype)
        if dims and all(d is not None for d in dims):
            return Arr(tuple(dims), arr.dtype)
        if len(args) == 1 and isinstance(args[0], TupV):
            return Arr(tuple(self.dim_of_value(x) for x in args[0].items),
                       arr.dtype)
        return Arr(None, arr.dtype)

    def _jnp_call(self, node, name, env, base):
        args = [self.eval(a, env, base) for a in node.args]
        if name in ("zeros", "ones", "empty", "full"):
            shape = self.shape_from_value(args[0]) if args else None
            if name == "full":
                dt = self._dtype_kw(node, env, base, pos=2)
                if dt is None and len(args) > 1:
                    fill = args[1]
                    if isinstance(fill, StaticV) and isinstance(
                            fill.value, bool):
                        dt = "bool"
            else:
                dt = self._dtype_kw(node, env, base, pos=1)
            if dt is None:
                # jnp.zeros((N,), bool)-style positional dtype
                pos = 2 if name == "full" else 1
                if len(node.args) > pos:
                    dt = self.dtype_from_expr(node.args[pos], env, base)
            return Arr(shape, dt or ("f64" if name != "full" else None))
        if name in ("zeros_like", "ones_like", "full_like", "empty_like"):
            src = args[0] if args else UNKNOWN
            pos = 2 if name == "full_like" else 1
            dt = self._dtype_kw(node, env, base, pos=pos)
            if isinstance(src, Arr):
                return Arr(src.shape, dt or src.dtype)
            return UNKNOWN
        if name == "asarray" or name == "array":
            dt = self._dtype_kw(node, env, base, pos=1)
            src = args[0] if args else UNKNOWN
            if isinstance(src, Arr):
                return Arr(src.shape, dt or src.dtype)
            if isinstance(src, (DimV, StaticV)):
                if dt is None and isinstance(src, StaticV):
                    if isinstance(src.value, bool):
                        dt = "bool"
                return Arr((), dt)
            if isinstance(src, TupV):
                return Arr((len(src.items),), dt)
            return Arr(None, dt)
        if name == "arange":
            dt = self._dtype_kw(node, env, base)
            if len(node.args) == 1 and args:
                d = self.dim_of_value(args[0])
                return Arr((d,), dt or "i64")
            if len(args) >= 2:
                lo = self.dim_of_value(args[0])
                hi = self.dim_of_value(args[1])
                if lo is not None and hi is not None and len(args) == 2:
                    return Arr((dim_add(hi, lo, -1),), dt or "i64")
            return Arr((None,), dt or "i64")
        if name == "broadcast_to":
            shape = self.shape_from_value(args[1]) if len(args) > 1 else None
            dt = args[0].dtype if args and isinstance(args[0], Arr) else None
            return Arr(shape, dt)
        if name in ("concatenate", "stack"):
            seq = args[0] if args else UNKNOWN
            axv = self._keyword(node, "axis")
            axis = 0
            if axv is not None:
                a = self.eval(axv, env, base)
                if isinstance(a, StaticV) and isinstance(a.value, int):
                    axis = a.value
                else:
                    return UNKNOWN
            elif len(node.args) > 1:
                a = args[1]
                if isinstance(a, StaticV) and isinstance(a.value, int):
                    axis = a.value
                else:
                    return UNKNOWN
            if not isinstance(seq, TupV) or not seq.items:
                return UNKNOWN
            parts = [p for p in seq.items]
            if not all(isinstance(p, Arr) for p in parts):
                return UNKNOWN
            dts = [p.dtype for p in parts if p.dtype is not None]
            dt = dts[0] if dts and all(d == dts[0] for d in dts) else None
            shapes = [p.shape for p in parts]
            if any(s is None for s in shapes):
                return Arr(None, dt)
            if name == "stack":
                # all inputs must agree; check named mismatches pairwise
                joinshape = self.broadcast_shapes(shapes, node)
                rank = len(shapes[0])
                ax = axis % (rank + 1)
                if joinshape is None or len(joinshape) != rank:
                    return Arr(None, dt)
                out = list(joinshape)
                out.insert(ax, len(parts))
                return Arr(tuple(out), dt)
            rank = len(shapes[0])
            if any(len(s) != rank for s in shapes):
                return Arr(None, dt)
            ax = axis % rank if rank else 0
            out = []
            for i in range(rank):
                if i == ax:
                    tot = 0
                    for s in shapes:
                        tot = dim_add(tot, s[i])
                    out.append(tot)
                else:
                    dims = [s[i] for s in shapes]
                    cur = dims[0]
                    for d in dims[1:]:
                        if cur is None or d is None:
                            cur = None
                        elif not dim_eq(cur, d):
                            if dim_is_named(cur) and dim_is_named(d):
                                self._shape_flag(
                                    node,
                                    f"concatenate along axis {ax} aligns "
                                    f"{dim_str(cur)} with {dim_str(d)} on "
                                    f"axis {i}",
                                )
                            cur = None
                    out.append(cur)
            return Arr(tuple(out), dt)
        if name == "einsum":
            return self._einsum(node, args, env, base)
        if name in ("take",):
            arr = args[1] if len(args) > 1 and isinstance(args[0], StaticV) \
                else (args[0] if args else UNKNOWN)
            # jnp.take(arr, idx, axis=?) — axis None flattens; default 0? jnp
            # take without axis flattens; the tree always passes 1-D arrays
            if len(args) >= 2 and isinstance(args[0], Arr) and isinstance(
                    args[1], Arr):
                src, idx = args[0], args[1]
                if src.shape is not None and src.shape and dim_is_node_axis(
                        src.shape[0]):
                    self._shard_flag(
                        node, "implicit gather",
                        f"jnp.take from an {NODE_AXIS}-leading array",
                    )
                if src.shape is not None and len(src.shape) == 1:
                    return Arr(idx.shape, src.dtype)
            return UNKNOWN
        if name == "take_along_axis":
            if len(args) >= 2 and isinstance(args[0], Arr) and isinstance(
                    args[1], Arr):
                return Arr(args[1].shape, args[0].dtype)
            return UNKNOWN
        if name in _SAME_SHAPE_FNS:
            src = args[0] if args else UNKNOWN
            if isinstance(src, Arr):
                dt = src.dtype
                if name in _BOOL_RESULT_FNS:
                    dt = "bool"
                if name in ("argsort",):
                    dt = None
                return Arr(src.shape, dt)
            return UNKNOWN
        if name in _REDUCERS:
            src = args[0] if args else UNKNOWN
            if isinstance(src, Arr):
                return self._reduce_call(node, src, name, env, base)
            return UNKNOWN
        if name in _BROADCAST_FNS:
            arrs = [a for a in args if isinstance(a, Arr)]
            if not arrs:
                return UNKNOWN
            if name in ("multiply", "add", "subtract", "power", "mod",
                        "floor_divide"):
                self._arith_dtype_checks(
                    node,
                    ast.Mult() if name == "multiply" else ast.Add(),
                    args,
                )
            shape = self.broadcast_shapes([a.shape for a in arrs], node)
            if name in _BOOL_RESULT_FNS:
                dt = "bool"
            elif name == "where":
                branch = [a for a in args[1:] if isinstance(a, Arr)]
                dts = [b.dtype for b in branch if b.dtype is not None]
                dt = dts[0] if len(dts) == len(branch) and branch and all(
                    d == dts[0] for d in dts) else (
                        dts[0] if len(branch) == 1 and dts else None)
                if len(args) >= 3:
                    shape = self.broadcast_shapes(
                        [a.shape for a in args if isinstance(a, Arr)], node
                    )
            elif name == "clip":
                dt = arrs[0].dtype
            else:
                dts = [a.dtype for a in arrs]
                dt = dts[0] if len(dts) >= 1 and all(
                    d == dts[0] for d in dts if d is not None
                ) and dts[0] is not None else None
            return Arr(shape, dt)
        if name == "pad":
            src = args[0] if args else UNKNOWN
            if isinstance(src, Arr):
                return Arr(None, src.dtype)
            return UNKNOWN
        if name == "iinfo" or name == "finfo":
            return StaticV()
        if name in ("searchsorted", "bincount", "unique", "nonzero",
                    "digitize"):
            return UNKNOWN
        if name == "dot":
            return UNKNOWN
        if name in ("matmul", "tensordot"):
            return UNKNOWN
        if name == "expand_dims":
            if args and isinstance(args[0], Arr) and args[0].shape is not None:
                axv = args[1] if len(args) > 1 else None
                if isinstance(axv, StaticV) and isinstance(axv.value, int):
                    out = list(args[0].shape)
                    ax = axv.value % (len(out) + 1)
                    out.insert(ax, 1)
                    return Arr(tuple(out), args[0].dtype)
            return UNKNOWN
        if name == "squeeze":
            return UNKNOWN
        if name == "tile":
            return UNKNOWN
        if name == "roll":
            if args and isinstance(args[0], Arr):
                return Arr(args[0].shape, args[0].dtype)
            return UNKNOWN
        return UNKNOWN

    def _einsum(self, node, args, env, base):
        if not node.args or not isinstance(node.args[0], ast.Constant) or \
                not isinstance(node.args[0].value, str):
            return UNKNOWN
        spec = node.args[0].value.replace(" ", "")
        if "->" not in spec or "..." in spec:
            return UNKNOWN
        ins, out = spec.split("->")
        in_specs = ins.split(",")
        operands = args[1:]
        if len(in_specs) != len(operands):
            return UNKNOWN
        letter_dim: Dict[str, object] = {}
        for sp, op in zip(in_specs, operands):
            if not isinstance(op, Arr) or op.shape is None or \
                    len(op.shape) != len(sp):
                for ch in sp:
                    letter_dim.setdefault(ch, None)
                continue
            for ch, d in zip(sp, op.shape):
                if ch in letter_dim:
                    prev = letter_dim[ch]
                    if prev is not None and d is not None and \
                            not dim_eq(prev, d):
                        if dim_is_named(prev) and dim_is_named(d):
                            self._shape_flag(
                                node,
                                f"einsum '{spec}' binds '{ch}' to both "
                                f"{dim_str(prev)} and {dim_str(d)}",
                            )
                        letter_dim[ch] = None
                else:
                    letter_dim[ch] = d
        contracted = [ch for ch in letter_dim if ch not in out]
        for ch in contracted:
            d = letter_dim.get(ch)
            if d is not None and dim_is_node_axis(d):
                self._shard_flag(
                    node, "einsum contraction",
                    f"'{spec}' contracts '{ch}' = {NODE_AXIS}",
                )
        dts = [op.dtype for op in operands if isinstance(op, Arr)]
        dt = dts[0] if dts and all(d == dts[0] for d in dts) else None
        return Arr(tuple(letter_dim.get(ch) for ch in out), dt)

    def _lax_call(self, node, name, env, base):
        args = [self.eval(a, env, base) for a in node.args]
        if name == "scan":
            return self._scan(node, args, env, base)
        if name == "while_loop":
            return self._while_loop(node, args, env, base)
        if name == "fori_loop":
            return self._fori_loop(node, args, env, base)
        if name == "cond":
            return self._cond(node, args, env, base)
        if name in ("cummax", "cummin", "cumsum", "cumprod",
                    "associative_scan"):
            src = args[0] if args else UNKNOWN
            if isinstance(src, Arr):
                return Arr(src.shape, src.dtype)
            return UNKNOWN
        if name == "dynamic_slice":
            if len(args) >= 3:
                sizes = self.shape_from_value(args[2])
                dt = args[0].dtype if isinstance(args[0], Arr) else None
                src = args[0]
                if isinstance(src, Arr) and src.shape is not None and \
                        src.shape and dim_is_node_axis(src.shape[0]):
                    # dynamic_slice READS across shards only when the start
                    # is traced — which it always is here; flag it
                    self._shard_flag(
                        node, "dynamic_slice",
                        f"windowed read of an {NODE_AXIS}-leading array",
                    )
                return Arr(sizes, dt)
            return UNKNOWN
        if name == "dynamic_update_slice":
            if len(args) >= 2 and isinstance(args[0], Arr):
                dst, upd = args[0], args[1]
                if isinstance(upd, Arr) and dst.shape is not None and \
                        upd.shape is not None and \
                        len(dst.shape) != len(upd.shape):
                    self._shape_flag(
                        node,
                        "dynamic_update_slice rank mismatch: "
                        f"{shape_str(dst.shape)} vs {shape_str(upd.shape)}",
                    )
                if dst.shape is not None and dst.shape and \
                        dim_is_node_axis(dst.shape[0]):
                    self._shard_flag(
                        node, "dynamic_update_slice",
                        f"windowed write into an {NODE_AXIS}-leading array",
                    )
                return Arr(dst.shape, dst.dtype)
            return UNKNOWN
        if name == "dot_general":
            return self._dot_general(node, args, env, base)
        if name in ("bitcast_convert_type", "convert_element_type"):
            dt = self.dtype_from_expr(node.args[1], env, base) \
                if len(node.args) > 1 else None
            if args and isinstance(args[0], Arr):
                return Arr(None, dt)
            return UNKNOWN
        if name in ("with_sharding_constraint", "stop_gradient"):
            # layout/AD annotations: identity on shape and dtype
            return args[0] if args else UNKNOWN
        if name == "top_k":
            return UNKNOWN
        if name == "slice":
            return UNKNOWN
        if name == "select":
            arrs = [a for a in args if isinstance(a, Arr)]
            if arrs:
                shape = self.broadcast_shapes([a.shape for a in arrs], node)
                return Arr(shape, arrs[-1].dtype)
            return UNKNOWN
        return UNKNOWN

    def _dot_general(self, node, args, env, base):
        if len(node.args) < 3:
            return UNKNOWN
        try:
            dims = ast.literal_eval(node.args[2])
        except (ValueError, SyntaxError):
            return UNKNOWN
        lhs, rhs = args[0], args[1]
        if not (isinstance(lhs, Arr) and isinstance(rhs, Arr)) or \
                lhs.shape is None or rhs.shape is None:
            return UNKNOWN
        (lc, rc), (lb, rb) = dims
        for i, j in zip(lc, rc):
            dl, dr = lhs.shape[i], rhs.shape[j]
            if dl is not None and dr is not None and not dim_eq(dl, dr):
                if dim_is_named(dl) and dim_is_named(dr):
                    self._shape_flag(
                        node,
                        f"dot_general contracts {dim_str(dl)} against "
                        f"{dim_str(dr)}",
                    )
            if (dl is not None and dim_is_node_axis(dl)) or (
                    dr is not None and dim_is_node_axis(dr)):
                self._shard_flag(
                    node, "dot_general contraction",
                    f"contracts the {NODE_AXIS} axis",
                )
        batch = [lhs.shape[i] for i in lb]
        lfree = [d for i, d in enumerate(lhs.shape)
                 if i not in lc and i not in lb]
        rfree = [d for i, d in enumerate(rhs.shape)
                 if i not in rc and i not in rb]
        dt = None
        pet = self._keyword(node, "preferred_element_type")
        if pet is not None:
            dt = self.dtype_from_expr(pet, env, base)
        elif lhs.dtype == rhs.dtype:
            dt = lhs.dtype
        return Arr(tuple(batch + lfree + rfree), dt)

    def _segment_call(self, node, name, env, base):
        if name not in ("segment_sum", "segment_max", "segment_min",
                        "segment_prod"):
            return UNKNOWN
        args = [self.eval(a, env, base) for a in node.args]
        data = args[0] if args else UNKNOWN
        nseg = None
        kw = self._keyword(node, "num_segments")
        if kw is not None:
            nseg = self.dim_of_value(self.eval(kw, env, base))
        elif len(args) > 2:
            nseg = self.dim_of_value(args[2])
        if isinstance(data, Arr) and data.shape is not None and data.shape:
            d0 = data.shape[0]
            crossing = (d0 is not None and dim_is_node_axis(d0)) or (
                nseg is not None and dim_is_named(nseg)
                and dim_of_sym(NODE_AXIS)[1][0][0] in dict(_as_lin(nseg)[1])
            )
            if crossing:
                self._shard_flag(
                    node, f"{name} segment op",
                    f"segments cross the {NODE_AXIS} axis "
                    f"(data {shape_str(data.shape)}, "
                    f"num_segments {dim_str(nseg)})",
                )
            return Arr((nseg,) + data.shape[1:], data.dtype)
        return UNKNOWN

    def _random_call(self, node, name, env, base):
        for a in node.args:
            self.eval(a, env, base)
        if name in ("bits", "uniform", "normal", "randint"):
            shp = self._keyword(node, "shape")
            sv = None
            if shp is not None:
                sv = self.shape_from_value(self.eval(shp, env, base))
            elif len(node.args) > 1:
                sv = self.shape_from_value(self.eval(node.args[1], env, base))
            dt = self._dtype_kw(node, env, base)
            return Arr(sv, dt)
        return UNKNOWN

    # -- higher-order: vmap / scan / while / cond --------------------------

    def _strip_lead(self, v, node):
        """Remove axis 0 from every array leaf (vmap operand view).
        Returns (stripped value, lead dim or None)."""
        if isinstance(v, Arr):
            if v.shape is None or not v.shape:
                return Arr(None, v.dtype), None
            return Arr(v.shape[1:], v.dtype), v.shape[0]
        if isinstance(v, TupV):
            outs, leads = [], []
            for it in v.items:
                s, l = self._strip_lead(it, node)
                outs.append(s)
                leads.append(l)
            lead = next((l for l in leads if l is not None), None)
            return TupV(outs), lead
        if isinstance(v, RecV):
            fields, lead = {}, None
            for k, it in v.fields.items():
                s, l = self._strip_lead(it, node)
                fields[k] = s
                if lead is None:
                    lead = l
            return RecV(v.cls, fields), lead
        return UNKNOWN, None

    def _prepend_lead(self, v, lead):
        if isinstance(v, Arr):
            if v.shape is None:
                return Arr(None, v.dtype)
            return Arr((lead,) + v.shape, v.dtype)
        if isinstance(v, TupV):
            return TupV([self._prepend_lead(it, lead) for it in v.items])
        if isinstance(v, DictV):
            return DictV({
                k: self._prepend_lead(it, lead) for k, it in v.entries.items()
            })
        if isinstance(v, RecV):
            return RecV(v.cls, {
                k: self._prepend_lead(it, lead) for k, it in v.fields.items()
            })
        return UNKNOWN

    def _vmap(self, node, env, base):
        if node.keywords:
            # in_axes/out_axes beyond the default are not modeled
            fn = self.eval(node.args[0], env, base) if node.args else UNKNOWN
            return _MappedV(fn, self, modeled=False)
        fn = self.eval(node.args[0], env, base) if node.args else UNKNOWN
        return _MappedV(fn, self, modeled=True)

    def _call_mapped(self, node, mapped, env, base):
        args = [self.eval(a, env, base) for a in node.args
                if not isinstance(a, ast.Starred)]
        if not mapped.modeled or any(isinstance(a, Unknown) for a in args):
            return UNKNOWN
        stripped, leads = [], []
        for a in args:
            s, l = self._strip_lead(a, node)
            stripped.append(s)
            leads.append(l)
        lead = None
        for l in leads:
            if l is None:
                continue
            if lead is None:
                lead = l
            elif not dim_eq(lead, l):
                if dim_is_named(lead) and dim_is_named(l):
                    self._shape_flag(
                        node,
                        f"vmap maps mismatched leading axes: "
                        f"{dim_str(lead)} vs {dim_str(l)}",
                    )
                lead = None
                break
        out = self._call_value(node, mapped.fn, stripped, {}, base)
        return self._prepend_lead(out, lead)

    def _scan(self, node, args, env, base):
        # jax.lax.scan(f, init, xs[, length=])
        if len(args) < 2:
            return UNKNOWN
        fn, init = args[0], args[1]
        xs = args[2] if len(args) > 2 else NONE
        length = None
        lkw = self._keyword(node, "length")
        if lkw is not None:
            length = self.dim_of_value(self.eval(lkw, env, base))
        x_stripped, lead = (UNKNOWN, length)
        if isinstance(xs, (Arr, TupV, RecV)):
            x_stripped, xlead = self._strip_lead(xs, node)
            lead = xlead if xlead is not None else length
        out = self._call_value(node, fn, [init, x_stripped], {}, base)
        carry_out, ys = UNKNOWN, UNKNOWN
        if isinstance(out, TupV) and len(out.items) == 2:
            carry_out, ys = out.items
        self._check_carry(node, "scan carry", init, carry_out)
        self._check_accum(node, init)
        return TupV([
            join(init, carry_out) if not isinstance(carry_out, Unknown)
            else UNKNOWN,
            self._prepend_lead(ys, lead),
        ])

    def _while_loop(self, node, args, env, base):
        if len(args) < 3:
            return UNKNOWN
        cond, body, init = args[0], args[1], args[2]
        self._call_value(node, cond, [init], {}, base)
        out = self._call_value(node, body, [init], {}, base)
        self._check_carry(node, "while_loop carry", init, out)
        self._check_accum(node, init)
        if isinstance(out, Unknown):
            return init
        return join(init, out)

    def _fori_loop(self, node, args, env, base):
        if len(args) < 4:
            return UNKNOWN
        body, init = args[2], args[3]
        out = self._call_value(node, body, [Arr((), "i64"), init], {}, base)
        self._check_carry(node, "fori_loop carry", init, out)
        self._check_accum(node, init)
        if isinstance(out, Unknown):
            return init
        return join(init, out)

    def _cond(self, node, args, env, base):
        if len(args) < 3:
            return UNKNOWN
        tf, ff = args[1], args[2]
        operands = args[3:] if len(args) > 3 else []
        tv = self._call_value(node, tf, operands, {}, base)
        fv = self._call_value(node, ff, operands, {}, base)
        return join(tv, fv)

    def _check_carry(self, node, what, init, out):
        """Structural comparison of loop-carry init vs body output —
        NAMED drifts are exactly what jax cannot see (the concrete sizes
        coincide)."""
        if isinstance(init, Unknown) or isinstance(out, Unknown):
            return
        self._walk_carry(node, what, init, out, path="")

    def _walk_carry(self, node, what, a, b, path):
        if isinstance(a, Unknown) or isinstance(b, Unknown):
            return
        loc = f" at {path}" if path else ""
        if isinstance(a, Arr) and isinstance(b, Arr):
            if a.shape is None or b.shape is None:
                return
            if len(a.shape) != len(b.shape):
                self._shape_flag(
                    node,
                    f"{what} drift{loc}: rank {len(a.shape)} "
                    f"{shape_str(a.shape)} vs rank {len(b.shape)} "
                    f"{shape_str(b.shape)}",
                )
                return
            for i, (da, db_) in enumerate(zip(a.shape, b.shape)):
                if da is None or db_ is None:
                    continue
                if not dim_eq(da, db_) and dim_is_named(da) and \
                        dim_is_named(db_):
                    self._shape_flag(
                        node,
                        f"{what} drift{loc}: axis {i} enters as "
                        f"{dim_str(da)} and leaves as {dim_str(db_)} "
                        f"({shape_str(a.shape)} vs {shape_str(b.shape)})",
                    )
            if a.dtype is not None and b.dtype is not None and \
                    a.dtype != b.dtype:
                self._dtype_flag(
                    node,
                    f"{what} dtype drift{loc}: enters {a.dtype}, leaves "
                    f"{b.dtype}",
                )
            return
        if isinstance(a, TupV) and isinstance(b, TupV):
            if len(a.items) != len(b.items):
                self._shape_flag(
                    node,
                    f"{what} drift{loc}: {len(a.items)} elements in, "
                    f"{len(b.items)} out",
                )
                return
            for i, (x, y) in enumerate(zip(a.items, b.items)):
                self._walk_carry(node, what, x, y, f"{path}[{i}]")
            return
        if isinstance(a, DictV) and isinstance(b, DictV):
            for k in set(a.entries) & set(b.entries):
                self._walk_carry(node, what, a.entries[k], b.entries[k],
                                 f"{path}[{k!r}]")
            return
        if isinstance(a, RecV) and isinstance(b, RecV) and a.cls == b.cls:
            for k in set(a.fields) & set(b.fields):
                self._walk_carry(node, what, a.fields[k], b.fields[k],
                                 f"{path}.{k}")

    def _check_accum(self, node, init):
        """Root-declared accumulation-dtype contract over loop carries."""
        contract = self._accum[-1] if self._accum else None
        if not contract:
            return
        leaves: List[Tuple[str, Arr]] = []

        def walk(v, path):
            if isinstance(v, Arr):
                leaves.append((path, v))
            elif isinstance(v, TupV):
                for i, it in enumerate(v.items):
                    walk(it, f"{path}[{i}]")
            elif isinstance(v, DictV):
                for k, it in v.entries.items():
                    walk(it, f"{path}[{k!r}]")
            elif isinstance(v, RecV):
                for k, it in v.fields.items():
                    walk(it, f"{path}.{k}")

        walk(init, "carry")
        for path, arr in leaves:
            if arr.dtype is not None and arr.dtype not in contract:
                self._dtype_flag(
                    node,
                    f"loop carry {path} has dtype {arr.dtype}, outside the "
                    f"root's declared accum({', '.join(sorted(contract))}) "
                    "contract",
                )

    # -- user-function calls (context-sensitive summaries) -----------------

    def _aval_key(self, v):
        if isinstance(v, Arr):
            return ("A", v.shape, v.dtype)
        if isinstance(v, TupV):
            return ("T",) + tuple(self._aval_key(i) for i in v.items)
        if isinstance(v, DictV):
            return ("D",) + tuple(
                (k, self._aval_key(x)) for k, x in sorted(v.entries.items())
            )
        if isinstance(v, RecV):
            return ("R", v.cls) + tuple(
                (k, self._aval_key(x)) for k, x in sorted(v.fields.items())
            )
        if isinstance(v, DimV):
            return ("d", v.lin)
        if isinstance(v, StaticV):
            try:
                hash(v.value)
                return ("s", v.value if v.value is not _UNSET else "?")
            except TypeError:
                return ("s", "?")
        if isinstance(v, FuncV):
            return ("f", id(v.node))
        if isinstance(v, CtorV):
            return ("c", v.cls)
        if isinstance(v, DtypeV):
            return ("dt", v.dt)
        if isinstance(v, ModV):
            return ("m", v.base)
        return ("u",)

    def _call_value(self, node, fn, args, kwargs, base):
        if isinstance(fn, _MappedV):
            return UNKNOWN
        if isinstance(fn, FuncV):
            return self._call_funcv_direct(node, fn, args, kwargs)
        if isinstance(fn, CtorV):
            return self._construct_direct(fn, args, kwargs)
        return UNKNOWN

    def _call_funcv(self, node, fv: FuncV, env, base):
        args, kwargs = self._args_kwargs(node, env, base)
        return self._call_funcv_direct(node, fv, args, kwargs)

    def _call_funcv_direct(self, node, fv: FuncV, args, kwargs):
        if len(self._stack) >= self.MAX_DEPTH:
            return UNKNOWN
        fnode = fv.node
        if isinstance(fnode, ast.Lambda):
            params = [a.arg for a in fnode.args.args]
            inner = dict(fv.env) if fv.env is not None else {}
            for p, a in zip(params, args):
                inner[p] = a
            for i in range(len(args), len(params)):
                inner[params[i]] = UNKNOWN
            return self.eval(fnode.body, inner, fv.base)
        # a named def: summary-memoized per (func, args, roster coverage,
        # active accum contract) — both context bits change which findings
        # a body emits, so a summary computed under one must not be reused
        # under another
        covered = self._covered()
        accum = self._accum[-1] if self._accum else None
        key = None
        if fv.key is not None:
            key = (fv.key, covered,
                   frozenset(accum) if accum else None,
                   tuple(self._aval_key(a) for a in args),
                   tuple(sorted(
                       (k, self._aval_key(v)) for k, v in kwargs.items()
                   )))
            if key in self.summaries:
                hit = self.summaries[key]
                if hit is _IN_PROGRESS:
                    return UNKNOWN
                # shell copy: callers mutate returned dicts/records in
                # place (the wave step extends pod_step's state) — the
                # cached summary must stay pristine
                return _copy_shell(hit)
            self.summaries[key] = _IN_PROGRESS
        env = dict(fv.env) if fv.env is not None else {}
        self._bind_params(fnode, args, kwargs, env, fv.base)
        if fv.key is not None:
            self._stack.append(fv.key)
        try:
            rets: List[object] = []
            self.exec_block(fnode.body, env, fv.base, rets)
            out = UNKNOWN
            if rets:
                out = rets[0]
                for r in rets[1:]:
                    out = join(out, r)
            else:
                out = NONE
        finally:
            if fv.key is not None:
                self._stack.pop()
        if key is not None:
            self.summaries[key] = out
            return _copy_shell(out)
        return out

    def _bind_params(self, fnode, args, kwargs, env, base):
        a = fnode.args
        params = [p.arg for p in a.args]
        defaults = list(a.defaults)
        # positional
        for i, p in enumerate(params):
            if i < len(args):
                env[p] = args[i]
            elif p in kwargs:
                env[p] = kwargs.pop(p)
            else:
                di = i - (len(params) - len(defaults))
                if 0 <= di < len(defaults):
                    env[p] = self.eval(defaults[di], env, base)
                else:
                    env[p] = UNKNOWN
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            name = p.arg
            if name in kwargs:
                env[name] = kwargs.pop(name)
            elif d is not None:
                env[name] = self.eval(d, env, base)
            else:
                env[name] = UNKNOWN
        for k, v in kwargs.items():
            env.setdefault(k, v)

    def _construct(self, node, ctor: CtorV, env, base):
        args, kwargs = self._args_kwargs(node, env, base)
        return self._construct_direct(ctor, args, kwargs)

    def _construct_direct(self, ctor: CtorV, args, kwargs):
        fields = {}
        for name, v in zip(ctor.field_order, args):
            fields[name] = v
        for k, v in kwargs.items():
            if k in ctor.field_order or not ctor.field_order:
                fields[k] = v
        return RecV(ctor.cls, fields)

    # -- statements --------------------------------------------------------

    def bind_target(self, target, value, env):
        if isinstance(target, ast.Name):
            env[target.id] = value
        elif isinstance(target, (ast.Tuple, ast.List)):
            elts = target.elts
            starred_at = next(
                (i for i, e in enumerate(elts) if isinstance(e, ast.Starred)),
                None,
            )
            if isinstance(value, TupV) and starred_at is None and \
                    len(value.items) == len(elts):
                for el, v in zip(elts, value.items):
                    self.bind_target(el, v, env)
            elif isinstance(value, TupV) and starred_at is not None and \
                    len(value.items) >= len(elts) - 1:
                head = elts[:starred_at]
                tail = elts[starred_at + 1:]
                for el, v in zip(head, value.items[: len(head)]):
                    self.bind_target(el, v, env)
                mid = value.items[len(head): len(value.items) - len(tail)]
                self.bind_target(elts[starred_at].value, TupV(mid), env)
                for el, v in zip(tail, value.items[len(value.items)
                                                   - len(tail):]):
                    self.bind_target(el, v, env)
            else:
                for el in elts:
                    self.bind_target(
                        el.value if isinstance(el, ast.Starred) else el,
                        UNKNOWN, env,
                    )
        # attribute / subscript writes: model dict-entry assignment
        elif isinstance(target, ast.Subscript):
            pass  # handled by caller (needs env lookup of the container)

    def _assign_subscript(self, target: ast.Subscript, value, env, base):
        cont = self.eval(target.value, env, base)
        if isinstance(cont, DictV):
            kv = self.eval(target.slice, env, base)
            if isinstance(kv, StaticV) and isinstance(kv.value, str):
                cont.entries[kv.value] = value
        # list index writes (pads[axis] = ...) are not modeled

    def exec_block(self, stmts, env, base, rets) -> bool:
        """Execute statements; returns True if the block TERMINATES
        (return / raise on every path) — terminated branches are skipped
        by if/else joins."""
        for st in stmts:
            if isinstance(st, ast.FunctionDef):
                qual = self._qual_for(st, base)
                env[st.name] = FuncV(qual, st, env, base)
                continue
            if isinstance(st, ast.Return):
                v = self.eval(st.value, env, base) if st.value is not None \
                    else NONE
                rets.append(v)
                return True
            if isinstance(st, ast.Raise):
                return True
            if isinstance(st, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                self._exec_assign(st, env, base)
                continue
            if isinstance(st, ast.If):
                t = self.truthiness(st.test, env, base)
                if t is True:
                    if self.exec_block(st.body, env, base, rets):
                        return True
                    continue
                if t is False:
                    if st.orelse and self.exec_block(st.orelse, env, base,
                                                     rets):
                        return True
                    continue
                env_a = dict(env)
                env_b = dict(env)
                term_a = self.exec_block(st.body, env_a, base, rets)
                term_b = self.exec_block(st.orelse, env_b, base, rets) \
                    if st.orelse else False
                if term_a and term_b:
                    return True
                if term_a:
                    env.clear()
                    env.update(env_b)
                elif term_b:
                    env.clear()
                    env.update(env_a)
                else:
                    merged = {}
                    for k in set(env_a) | set(env_b):
                        if k in env_a and k in env_b:
                            merged[k] = join(env_a[k], env_b[k])
                        else:
                            merged[k] = env_a.get(k, env_b.get(k))
                    env.clear()
                    env.update(merged)
                continue
            if isinstance(st, ast.For):
                self._exec_for(st, env, base, rets)
                continue
            if isinstance(st, ast.While):
                self.eval(st.test, env, base)
                snap = dict(env)
                self.exec_block(st.body, env, base, rets)
                for k in set(env) | set(snap):
                    if k in env and k in snap:
                        env[k] = join(env[k], snap[k])
                self.exec_block(st.body, env, base, rets)
                continue
            if isinstance(st, ast.Expr):
                self.eval(st.value, env, base)
                continue
            if isinstance(st, (ast.Assert,)):
                self.eval(st.test, env, base)
                continue
            if isinstance(st, (ast.Global, ast.Nonlocal, ast.Pass,
                               ast.Import, ast.ImportFrom, ast.Delete,
                               ast.Break, ast.Continue)):
                continue
            if isinstance(st, ast.With):
                for item in st.items:
                    v = self.eval(item.context_expr, env, base)
                    if item.optional_vars is not None:
                        self.bind_target(item.optional_vars, UNKNOWN, env)
                self.exec_block(st.body, env, base, rets)
                continue
            if isinstance(st, ast.Try):
                self.exec_block(st.body, env, base, rets)
                for h in st.handlers:
                    self.exec_block(h.body, env, base, rets)
                self.exec_block(st.orelse, env, base, rets)
                self.exec_block(st.finalbody, env, base, rets)
                continue
            # anything else: walk sub-blocks conservatively
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(st, attr, None)
                if sub:
                    self.exec_block(sub, env, base, rets)
        return False

    def _qual_for(self, fnode, base):
        mi = self.mods.get(base)
        if mi is not None:
            for qual, rec in mi.funcs.items():
                if rec.node is fnode:
                    return rec.key
        return None

    def _exec_assign(self, st, env, base):
        if isinstance(st, ast.AugAssign):
            synthetic = ast.BinOp(
                left=st.target, op=st.op, right=st.value,
            )
            ast.copy_location(synthetic, st)
            ast.fix_missing_locations(synthetic)
            v = self.eval(synthetic, env, base)
            if isinstance(st.target, ast.Name):
                env[st.target.id] = v
            elif isinstance(st.target, ast.Subscript):
                self._assign_subscript(st.target, v, env, base)
            return
        value_node = st.value
        targets = st.targets if isinstance(st, ast.Assign) else [st.target]
        if value_node is None:
            return
        v = self.eval(value_node, env, base)
        for t in targets:
            if isinstance(t, ast.Subscript):
                self._assign_subscript(t, v, env, base)
            else:
                self.bind_target(t, v, env)

    def _exec_for(self, st, env, base, rets):
        it = self.eval(st.iter, env, base)
        # literal-tuple iteration unrolls precisely (the reason_counts /
        # DIAG_KERNELS idiom builds fixed-length lists this way)
        if isinstance(it, TupV) and len(it.items) <= 32:
            for item in it.items:
                self.bind_target(st.target, item, env)
                self.exec_block(st.body, env, base, rets)
            self.exec_block(st.orelse, env, base, rets)
            return
        # symbolic ranges: two joined passes reach the accumulator fixpoint
        if isinstance(it, Arr) and it.shape is not None and it.shape:
            elem = Arr(it.shape[1:], it.dtype)
        else:
            elem = UNKNOWN
        self.bind_target(st.target, elem, env)
        snap = dict(env)
        self.exec_block(st.body, env, base, rets)
        for k in set(env) & set(snap):
            env[k] = join(env[k], snap[k])
        self.exec_block(st.body, env, base, rets)
        self.exec_block(st.orelse, env, base, rets)

    # -- roots -------------------------------------------------------------

    def _analyze_root(self, rec: _FuncRec, ann: RootAnnotation) -> None:
        fnode = rec.node
        params = [p.arg for p in fnode.args.args] + \
            [p.arg for p in fnode.args.kwonlyargs]
        for name in ann.axes:
            if name not in params:
                self.emit(
                    RULE_SHAPE, rec.mod, ann.line or fnode.lineno,
                    f"{rec.qual}: axes() names '{name}' but the root has no "
                    f"such parameter",
                )
        jd = _jit_decoration(fnode)
        static_names = jd[1] if jd else set()
        env: Dict[str, object] = {}
        all_args = fnode.args.args + fnode.args.kwonlyargs
        defaults = {}
        pos = fnode.args.args
        for p, d in zip(pos[len(pos) - len(fnode.args.defaults):],
                        fnode.args.defaults):
            defaults[p.arg] = d
        for p, d in zip(fnode.args.kwonlyargs, fnode.args.kw_defaults):
            if d is not None:
                defaults[p.arg] = d
        for p in all_args:
            name = p.arg
            if name in ann.axes:
                env[name] = spec_to_aval(
                    ann.axes[name], self.class_tables, ns=name
                )
            elif name in static_names:
                is_int = (
                    isinstance(p.annotation, ast.Name)
                    and p.annotation.id == "int"
                )
                sv = ann.static_values.get(name, _UNSET)
                if isinstance(sv, int) and not isinstance(sv, bool):
                    env[name] = DimV(dim_of_sym(name))
                elif sv is not _UNSET:
                    env[name] = StaticV(sv)
                elif is_int:
                    env[name] = DimV(dim_of_sym(name))
                elif name in defaults:
                    # a LITERAL default (True/False/tuples) prunes to the
                    # branch the runtime cross-check will trace
                    env[name] = self.eval(defaults[name], {}, rec.base)
                else:
                    env[name] = StaticV()
            elif name in defaults:
                env[name] = self.eval(defaults[name], {}, rec.base)
            else:
                env[name] = UNKNOWN
        self._stack.append(rec.key)
        self._accum.append(ann.accum)
        try:
            rets: List[object] = []
            self.exec_block(fnode.body, env, rec.base, rets)
            out: object = UNKNOWN
            if rets:
                out = rets[0]
                for r in rets[1:]:
                    out = join(out, r)
            self.root_returns[f"{rec.base}.{rec.qual}"] = out
        finally:
            self._accum.pop()
            self._stack.pop()


class _MappedV:
    """jax.vmap(fn) — callable wrapper carrying the mapped function."""

    __slots__ = ("fn", "engine", "modeled")

    def __init__(self, fn, engine, modeled):
        self.fn = fn
        self.engine = engine
        self.modeled = modeled


NOT_BUILTIN = object()
_IN_PROGRESS = object()


def _copy_shell(v):
    """Copy mutable containers (Arrs are immutable and shared)."""
    if isinstance(v, TupV):
        return TupV([_copy_shell(i) for i in v.items])
    if isinstance(v, DictV):
        return DictV({k: _copy_shell(x) for k, x in v.entries.items()})
    if isinstance(v, RecV):
        return RecV(v.cls, {k: _copy_shell(x) for k, x in v.fields.items()})
    return v

_PYOPS = {
    ast.Add: lambda a, b: a + b,
    ast.Sub: lambda a, b: a - b,
    ast.Mult: lambda a, b: a * b,
    ast.Div: lambda a, b: a / b,
    ast.FloorDiv: lambda a, b: a // b,
    ast.Mod: lambda a, b: a % b,
    ast.Pow: lambda a, b: a ** b,
    ast.LShift: lambda a, b: a << b,
    ast.RShift: lambda a, b: a >> b,
    ast.BitAnd: lambda a, b: a & b,
    ast.BitOr: lambda a, b: a | b,
    ast.BitXor: lambda a, b: a ^ b,
}
_PYCMP = {
    ast.Eq: lambda a, b: a == b,
    ast.NotEq: lambda a, b: a != b,
    ast.Lt: lambda a, b: a < b,
    ast.LtE: lambda a, b: a <= b,
    ast.Gt: lambda a, b: a > b,
    ast.GtE: lambda a, b: a >= b,
    ast.In: lambda a, b: a in b,
    ast.NotIn: lambda a, b: a not in b,
}


# ---------------------------------------------------------------------------
# checkers
# ---------------------------------------------------------------------------


def engine_for(mods: Sequence[SourceModule], cache: Optional[dict] = None):
    """Run (or reuse) the interpreter over a target set.  ``cache`` lets
    run_analysis share ONE interpretation across the shape/dtype/shard
    rule families (the per-rule wall time then lands on whichever family
    ran first — by construction the shape checker)."""
    key = tuple(m.path for m in mods)
    if cache is not None and key in cache:
        return cache[key]
    engine = ShapeEngine().run(mods)
    if cache is not None:
        cache[key] = engine
    return engine


class _EngineChecker(Checker):
    def run(self, mods: Sequence[SourceModule],
            engine_cache: Optional[dict] = None) -> None:
        engine = engine_for(mods, engine_cache)
        for rule, mod, line, msg in engine.raw_findings:
            if rule == self.rule:
                self.emit(mod, line, msg)


class ShapeChecker(_EngineChecker):
    rule = RULE_SHAPE


class DtypeChecker(_EngineChecker):
    rule = RULE_DTYPE


class ShardChecker(_EngineChecker):
    rule = RULE_SHARD


def collective_roster(mods: Sequence[SourceModule]) -> Dict[str, Dict]:
    """The parsed ``_KTPU_N_COLLECTIVES`` inventory across ``mods``:
    ``{module path: {qual: {reason, resolved, mechanism, line}}}`` — the
    machine-readable multichip burn-down (MULTICHIP.md inventory table,
    tests/test_static_analysis roster gate)."""
    engine = ShapeEngine()
    for m in mods:
        engine._index(m)
    out: Dict[str, Dict] = {}
    for mi in engine.mods.values():
        if not mi.roster:
            continue
        entries = {}
        for qual, reason in sorted(mi.roster.items()):
            m2 = RESOLVED_ROSTER_RE.match(reason)
            entries[qual] = {
                "reason": reason,
                "resolved": bool(m2),
                "mechanism": m2.group(1) if m2 else None,
                "line": mi.roster_lines.get(qual, 1),
            }
        out[mi.mod.path] = entries
    return out


# ---------------------------------------------------------------------------
# root summaries for the runtime cross-check (shapecheck.py)
# ---------------------------------------------------------------------------


# content-keyed engine cache for root_summaries: the runtime cross-check
# calls it once per size draw (the property test: 8+ draws per session),
# and the interpretation depends only on the SOURCES, not the sizes
_SUMMARY_CACHE: Dict[tuple, "ShapeEngine"] = {}


def root_summaries(mods: Sequence[SourceModule]):
    """[(root key 'module.qual', _FuncRec, RootAnnotation, inferred return
    aval)] for every annotated jit root — the static half the runtime
    eval_shape cross-check compares against."""
    key = tuple((m.path, hash(m.source)) for m in mods)
    engine = _SUMMARY_CACHE.get(key)
    if engine is None:
        engine = ShapeEngine().run(mods)
        if len(_SUMMARY_CACHE) > 8:
            _SUMMARY_CACHE.clear()
        _SUMMARY_CACHE[key] = engine
    out = []
    for rec, ann in engine.roots:
        out.append((
            f"{rec.base}.{rec.qual}",
            rec,
            ann,
            engine.root_returns.get(f"{rec.base}.{rec.qual}", UNKNOWN),
            engine,
        ))
    return out
