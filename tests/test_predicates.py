"""Prover unit tests + hypothesis soundness properties (§4.2)."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.predicates import (
    And,
    Cmp,
    ColCmp,
    Conjunction,
    Coverage,
    InSet,
    TRUE,
    evaluate,
    evaluate_conj,
    pred_and,
    prove_implies,
)

# -- direct cases -----------------------------------------------------------


def test_range_containment():
    p = Cmp("d", "<", 10)
    q = Cmp("d", "<", 20)
    assert prove_implies(p, q)
    assert not prove_implies(q, p)


def test_conjunction_containment():
    p = And((Cmp("seg", "==", 1), Cmp("d", "<", 10)))
    q = And((Cmp("seg", "==", 1), Cmp("d", "<", 20)))
    assert prove_implies(p, q)
    assert not prove_implies(q, p)
    # differing equality -> no containment
    r = And((Cmp("seg", "==", 2), Cmp("d", "<", 20)))
    assert not prove_implies(p, r)


def test_missing_constraint_is_weaker():
    p = Cmp("d", "<", 10)
    q = And((Cmp("d", "<", 20), Cmp("seg", "==", 1)))
    assert not prove_implies(p, q)  # p says nothing about seg
    assert prove_implies(And((Cmp("d", "<", 5), Cmp("seg", "==", 1))), q)


def test_inset_containment():
    p = InSet("n", frozenset((1.0, 2.0)))
    q = InSet("n", frozenset((1.0, 2.0, 3.0)))
    assert prove_implies(p, q)
    assert not prove_implies(q, p)
    assert prove_implies(Cmp("n", "==", 2.0), q)


def test_outside_fragment_unproven():
    p = ColCmp("a", "<", "b")  # cross-column: an opaque atom, not an interval
    assert not prove_implies(p, Cmp("a", "<", 5))
    assert not prove_implies(Cmp("a", "<", 5), p)
    assert Conjunction.from_pred(p).atoms == {("a", "<", "b")}
    # only the identical comparison (sides swapped or not) proves it
    assert prove_implies(And((p, Cmp("a", "<", 5))), ColCmp("b", ">", "a"))
    assert not prove_implies(p, ColCmp("a", "<=", "b"))


def test_coverage_interval_merge():
    cov = Coverage()
    cov.add(Conjunction.from_pred(And((Cmp("seg", "==", 1), Cmp("d", "<", 10)))))
    band = Conjunction.from_pred(
        And((Cmp("seg", "==", 1), Cmp("d", ">=", 10), Cmp("d", "<", 20)))
    )
    cov.add(band)
    # merged coverage must cover the union extent
    assert cov.covers(Conjunction.from_pred(And((Cmp("seg", "==", 1), Cmp("d", "<", 20)))))
    # but not a different segment
    assert not cov.covers(Conjunction.from_pred(And((Cmp("seg", "==", 2), Cmp("d", "<", 5)))))


# -- hypothesis: soundness of the prover over random conjunctions ------------

attr = st.sampled_from(["a", "b", "c"])
bound = st.integers(min_value=-20, max_value=20)
op = st.sampled_from(["<", "<=", ">", ">=", "=="])


@st.composite
def conj(draw):
    n = draw(st.integers(1, 4))
    return And(tuple(Cmp(draw(attr), draw(op), float(draw(bound))) for _ in range(n)))


@given(conj(), conj(), st.integers(0, 2**31 - 1))
@settings(max_examples=200, deadline=None)
def test_prove_implies_sound(p, q, seed):
    """If the prover says P => Q, then every row satisfying P satisfies Q."""
    rng = np.random.default_rng(seed)
    cols = {k: rng.integers(-25, 25, 300).astype(np.float64) for k in ("a", "b", "c")}
    if prove_implies(p, q):
        mp, mq = evaluate(p, cols), evaluate(q, cols)
        assert not (mp & ~mq).any()


@given(conj(), st.integers(0, 2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_canonical_eval_equivalence(p, seed):
    """Canonicalization preserves semantics."""
    c = Conjunction.from_pred(p)
    rng = np.random.default_rng(seed)
    cols = {k: rng.integers(-25, 25, 200).astype(np.float64) for k in ("a", "b", "c")}
    np.testing.assert_array_equal(evaluate(p, cols), evaluate_conj(c, cols))


@given(st.lists(conj(), min_size=1, max_size=4), conj(), st.integers(0, 2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_coverage_covers_sound(extents, probe, seed):
    """covers(B) -> every row of B lies in the union of the extents."""
    cov = Coverage()
    cs = []
    for e in extents:
        c = Conjunction.from_pred(e)
        cs.append(c)
        cov.add(c)
    b = Conjunction.from_pred(probe)
    if cov.covers(b):
        rng = np.random.default_rng(seed)
        cols = {k: rng.integers(-25, 25, 400).astype(np.float64) for k in ("a", "b", "c")}
        mb = evaluate_conj(b, cols)
        mu = np.zeros_like(mb)
        for c in cs:
            mu |= evaluate_conj(c, cols)
        assert not (mb & ~mu).any()
