"""``ops.common.eval_table`` against the golden scalar semantics
(``api.labels.Requirement.matches``), directly.

Every case is one term: a requirement under test, alone (term 1: a padded
requirement slot beside it) and in a conjunction with a second requirement
(term 0), evaluated against every label row below on the device and on the
host.  The same cases run through the call shapes the kernels use; two of
them take the per-(term, row) parse of the label integers and two the
per-(key, row) parse (``eval_table`` reads the choice off its static shapes),
so the two forms are held to the same host answers — and to each other.

The structural guards at the end need no chip: they trace ``eval_table`` at
the shapes of ``interpod-5k.backlog``'s precompute and count the elements
gathered from ``val_ints``.  A per-(term, row) parse there cost 2.8 s of a
4.9 s window on the chip (PERF.md, PR 31).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api import labels as L
from kubernetes_tpu.ops.common import DTable, eval_table
from kubernetes_tpu.snapshot.interner import ABSENT, INT_INVALID, Vocab
from kubernetes_tpu.snapshot.schema import pack_conjunction_table
from kubernetes_tpu.snapshot.selectors import CompiledRequirements

OPS = (L.IN, L.NOT_IN, L.EXISTS, L.DOES_NOT_EXIST, L.GT, L.LT)
KINDS = (
    "matching",
    "non_matching",
    "absent_key",
    "non_integer_label",
    "non_integer_rhs",
    "label_id_beyond_val_ints",
    "key_beyond_label_columns",
    "padded_slot",
    "invalid_term",
)
K = 8  # label columns, as the cells pack them (bucket_cap of the key vocabulary)

# The label rows every case is evaluated against.  "late-*" values are
# interned last and fall beyond the (cut) integer table.
ROWS = (
    {"tier": "5", "zone": "a"},
    {"tier": "12", "zone": "b"},
    {"zone": "a"},  # tier absent
    {"tier": "gold", "zone": "c"},  # not an integer
    {"tier": "-3"},
    {"tier": "99", "zone": "late-zone"},  # ids beyond val_ints' length
    {"tier": "77"},  # the same
    {},
)
LATE_VALUES = ("99", "77", "late-zone")
CONJUNCT = L.Requirement("zone", L.NOT_IN, ("c",))


def _requirement(op: str, kind: str) -> L.Requirement:
    """The requirement under test: what ``kind`` says is true of it on at
    least one of ROWS."""
    key = {"key_beyond_label_columns": "ghost"}.get(kind, "tier")
    if op in (L.EXISTS, L.DOES_NOT_EXIST):
        # row 0 has "tier"; no row has "nowhere"
        if kind in ("matching", "non_matching") and (op == L.EXISTS) != (kind == "matching"):
            key = "nowhere"
        return L.Requirement(key, op, ())
    if kind == "non_integer_rhs":
        return L.Requirement(key, op, ("x3",))
    if kind == "non_integer_label":
        return L.Requirement(key, op, ("gold",) if op in (L.IN, L.NOT_IN) else ("3",))
    if kind == "label_id_beyond_val_ints":
        return L.Requirement(key, op, ("99", "5") if op in (L.IN, L.NOT_IN) else ("50",))
    if op in (L.IN, L.NOT_IN):
        hit = (op == L.IN) == (kind != "non_matching")  # should row 0 (tier=5) be in the set?
        return L.Requirement(key, op, ("5", "7") if hit else ("7", "12"))
    # Gt / Lt against row 0's tier = 5
    above = (op == L.GT) == (kind != "non_matching")
    return L.Requirement(key, op, ("3",) if above else ("9",))


CASES = [(op, kind) for op in OPS for kind in KINDS]


class Packed:
    """Vocabulary, label columns, integer table and the cases' table."""

    def __init__(self):
        vocab = Vocab()
        for k in ("tier", "zone", "nowhere"):
            vocab.label_keys.intern(k)
        while len(vocab.label_keys) < K:  # "ghost" then lies beyond the K label columns
            vocab.label_keys.intern(f"filler-{len(vocab.label_keys)}")
        self.ghost = vocab.label_keys.intern("ghost")
        reqs = [_requirement(op, kind) for op, kind in CASES]
        values = [v for row in ROWS for v in row.values()]
        values += [v for r in reqs + [CONJUNCT] for v in r.values]
        for v in values:
            if v not in LATE_VALUES:
                vocab.intern_val(v)
        # the integer table stops before the late values; its last entry is
        # what a clipped id reads
        self.val_ints = np.asarray(vocab.val_ints(), np.int32)
        for v in LATE_VALUES:
            vocab.intern_val(v)
        self.vocab = vocab
        terms = []
        for req in reqs:
            both, alone = CompiledRequirements(), CompiledRequirements()
            both.add(req.key, req.op, req.values, vocab)
            both.add(CONJUNCT.key, CONJUNCT.op, CONJUNCT.values, vocab)
            alone.add(req.key, req.op, req.values, vocab)
            terms.append([both, alone])
        self.label_vals = np.full((len(ROWS), K), ABSENT, np.int32)
        for n, row in enumerate(ROWS):
            for k, v in row.items():
                self.label_vals[n, vocab.label_keys.lookup(k)] = vocab.label_vals.lookup(v)
        table = pack_conjunction_table(terms, t_cap=2, r_cap=2, v_cap=2)
        for i, (_op, kind) in enumerate(CASES):
            if kind == "invalid_term":
                table.term_valid[i, :] = False
        self.table = table
        self.want = self._expected()

    def seen_by_device(self, row: dict) -> dict:
        """The labels as the kernel reads their integers: an id beyond the
        integer table reads its last entry."""
        last = int(self.val_ints[-1])
        stand_in = "not-an-integer" if last == INT_INVALID else str(last)
        return {k: (stand_in if self.vocab.label_vals.lookup(v) >= len(self.val_ints) else v)
                for k, v in row.items()}

    def _expected(self) -> np.ndarray:
        """[case, term, row] from ``Requirement.matches``."""
        out = np.zeros((len(CASES), 2, len(ROWS)), bool)
        for i, (op, kind) in enumerate(CASES):
            if kind == "invalid_term":
                continue
            req = _requirement(op, kind)
            for n, row in enumerate(ROWS):
                # In / NotIn / Exists compare value ids, which the cut does not touch
                labels = self.seen_by_device(row) if op in (L.GT, L.LT) else row
                alone = req.matches(labels)
                out[i, 0, n] = alone and CONJUNCT.matches(row)
                out[i, 1, n] = alone
        return out


def _without_requirements(tbl: DTable) -> DTable:
    return DTable(tbl.req_key[..., :0], tbl.req_op[..., :0], tbl.req_vals[..., :0, :],
                  tbl.req_rhs[..., :0], tbl.term_valid)


def _pods_terms(p: Packed, tbl: DTable):  # lead = (P, T), [N, K] label rows
    return np.asarray(jax.jit(eval_table)(tbl, p.label_vals, p.val_ints))


def _terms_one(p: Packed, tbl: DTable):  # lead = (M, 1)
    P, T = tbl.term_valid.shape
    flat = jax.tree_util.tree_map(lambda a: a.reshape((P * T, 1) + a.shape[2:]), tbl)
    out = jax.jit(eval_table)(flat, p.label_vals, p.val_ints)
    return np.asarray(out).reshape(P, T, -1)


def _vmapped_row(p: Packed, tbl: DTable):  # each pod's terms against ONE row: lbl[None, :]
    ints = jnp.asarray(p.val_ints)
    one = jax.jit(jax.vmap(lambda t, lbl: eval_table(t, lbl[None, :], ints)[..., 0]))
    P = tbl.term_valid.shape[0]
    cols = [one(tbl, jnp.broadcast_to(p.label_vals[n], (P, K))) for n in range(len(ROWS))]
    return np.stack([np.asarray(c) for c in cols], axis=-1)


def _one_pod(p: Packed, tbl: DTable):  # lead = (1, T): one pod's terms against every row
    one = jax.jit(eval_table)
    P = tbl.term_valid.shape[0]
    rows = [one(jax.tree_util.tree_map(lambda a: a[i:i + 1], tbl), p.label_vals, p.val_ints)
            for i in range(P)]
    return np.concatenate([np.asarray(r) for r in rows], axis=0)


SHAPES = {"pods_terms": _pods_terms, "terms_one": _terms_one,
          "vmapped_row": _vmapped_row, "one_pod": _one_pod}


@pytest.fixture(scope="module")
def packed():
    return Packed()


@pytest.fixture(scope="module")
def evaluated(packed):
    tbl = DTable.host_tree(packed.table)
    return {name: fn(packed, tbl) for name, fn in SHAPES.items()}


def test_the_cases_are_what_their_names_say(packed):
    """The premises: ids beyond the table, a key beyond the columns, both
    verdicts present for every operator."""
    assert all(packed.vocab.label_vals.lookup(v) >= len(packed.val_ints) for v in LATE_VALUES)
    assert packed.ghost >= K
    assert packed.val_ints[packed.vocab.label_vals.lookup("gold")] == INT_INVALID
    want = packed.want
    for i, (op, kind) in enumerate(CASES):
        if kind == "matching":
            assert want[i, 1, 0], (op, kind)
        if kind == "non_matching":
            assert not want[i, 1, 0], (op, kind)
    for op in OPS:
        rows = [i for i, (o, k) in enumerate(CASES) if o == op and k != "invalid_term"]
        assert want[rows].any() and not want[rows].all(), op


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("op,kind", CASES, ids=[f"{op}-{kind}" for op, kind in CASES])
def test_eval_table_equals_requirement_matches(packed, evaluated, shape, op, kind):
    i = CASES.index((op, kind))
    got, want = evaluated[shape][i], packed.want[i]
    assert got.shape == want.shape
    assert (got == want).all(), f"{_requirement(op, kind)} on {ROWS}: device {got.tolist()}, host {want.tolist()}"


@pytest.mark.parametrize("shape", list(SHAPES))
def test_no_requirement_slots_match_every_row_of_a_valid_term(packed, shape):
    """R = 0: a term with no requirement matches everything, an invalid one nothing."""
    tbl = _without_requirements(DTable.host_tree(packed.table))
    got = SHAPES[shape](packed, tbl)
    want = np.broadcast_to(packed.table.term_valid[..., None], got.shape)
    assert got.dtype == bool and (got == want).all()


# ---- which parse a call shape takes, and what it may gather -------------------


def _gathered_from(jaxpr, tracked) -> list:
    """Element counts of every ``gather`` whose operand is one of ``tracked``
    (followed into the sub-jaxprs an equation carries)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and any(eqn.invars[0] is t for t in tracked):
            out.append(math.prod(eqn.outvars[0].aval.shape))
        for sub in eqn.params.values():
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns") and len(inner.invars) == len(eqn.invars):
                passed = [iv for iv, ov in zip(inner.invars, eqn.invars) if any(ov is t for t in tracked)]
                out += _gathered_from(inner, passed)
    return out


def _val_ints_gathers(lead, R, V, N, k, vv) -> list:
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    tbl = DTable(i32(*lead, R), i32(*lead, R), i32(*lead, R, V), i32(*lead, R),
                 jax.ShapeDtypeStruct(lead, jnp.bool_))
    closed = jax.make_jaxpr(eval_table)(tbl, i32(N, k), i32(vv))
    return _gathered_from(closed.jaxpr, [closed.jaxpr.invars[-1]])  # val_ints: the last leaf


# interpod-5k.backlog's precompute (PERF.md section 5): P = 512 pods, A = 4
# affinity terms, E = bucket_cap(10,512) existing-pod rows, N = 5,120 node
# rows, K = 8 label columns, R = V = 1, 5,120 label values
CELL_CALLS = {
    "inc_sel[P,AT,E]": ((512, 4), 11264),
    "sel[P,C,E]": ((512, 1), 11264),
    "ext_sel[M,1,P]": ((11264, 1), 512),
    "node_sel[P,T,N]": ((512, 1), 5120),
}


@pytest.mark.parametrize("call", list(CELL_CALLS))
def test_the_cells_calls_parse_integers_once_per_key_and_row(call):
    lead, rows = CELL_CALLS[call]
    sizes = _val_ints_gathers(lead, 1, 1, rows, 8, 5120)
    assert sizes, "no gather from val_ints found: the guard reads nothing"
    assert sum(sizes) <= 8 * rows, (
        f"{call}: {sizes} elements gathered from val_ints; a parse per (term, row) would gather "
        f"{math.prod(lead) * rows}, the label columns hold {8 * rows}")


@pytest.mark.parametrize("lead,R,k,after_select", [
    ((512, 4), 1, 8, False),
    ((1, 2), 2, 8, True),   # the one_pod shape above
    ((2,), 2, 8, True),     # the vmapped_row shape above, inside the vmap
    ((1, 1), 1, 64, True),  # one pod against a wide label vocabulary
    ((8, 1), 1, 8, True),   # as many (term, slot) pairs as columns: no gain
    ((9, 1), 1, 8, False),
])
def test_the_parse_is_done_on_the_side_with_fewer_elements(lead, R, k, after_select):
    rows = 256
    sizes = _val_ints_gathers(lead, R, 1, rows, k, 40)
    per_term_row, per_key_row = math.prod(lead) * R * rows, k * rows
    assert sum(sizes) == (per_term_row if after_select else per_key_row)
    assert sum(sizes) == min(per_term_row, per_key_row)
