"""Property tests for the completion-budget protocol (paper §4.5, §4.6.2).

Pins the four contracts the dynamism plane leans on:

* a reject signal never *raises* an initialized budget;
* an accept signal never *lowers* an initialized budget;
* out-of-order delivery of a set of same-type signals converges to the same
  final budget (the min/max against ``beta_old`` makes the update a lattice
  operation over the candidates, so permutation-invariant);
* a uniform clock skew ``sigma`` applied to every timestamp cancels: the
  protocol only ever consumes durations (§4.6.2).

Requires the optional ``hypothesis`` test dependency (declared in
pyproject.toml under ``[project.optional-dependencies] test``); the module
is skipped cleanly when it is not installed.
"""

import math

import pytest

hypothesis = pytest.importorskip("hypothesis")

from hypothesis import example, given, settings, strategies as st

from repro.core.budget import TaskBudget
from repro.core.events import AcceptSignal, EventRecord, RejectSignal


def xi(b):
    return 0.05 + 0.01 * b


def make_budget(m_max=25):
    return TaskBudget("T", xi, m_max=m_max)


records = st.builds(
    EventRecord,
    departure=st.floats(0.01, 30.0),
    queuing=st.floats(0.0, 5.0),
    batch_size=st.integers(1, 25),
    xi=st.floats(0.01, 1.0),
)

rejects = st.builds(
    RejectSignal,
    event_id=st.just(0),  # rebound to the record id by the tests
    epsilon=st.floats(0.0, 10.0),
    q_bar=st.floats(0.0, 10.0),
)

accepts = st.builds(
    AcceptSignal,
    event_id=st.just(0),
    epsilon=st.floats(0.0, 10.0),
    xi_bar=st.floats(0.0, 10.0),
)

# Shrunk cases: a zero excess over a denormal average (0 * inf) must leave
# the share at 0, not NaN.
DENORMAL = 5e-324
QUEUED = EventRecord(departure=1.0, queuing=1.0, batch_size=1, xi=1.0)
REJECT_0_DENORMAL = RejectSignal(0, 0.0, DENORMAL)
ACCEPT_0_DENORMAL = AcceptSignal(0, 0.0, DENORMAL)


# --------------------------------------------------------------------- #
# Monotonicity                                                           #
# --------------------------------------------------------------------- #
@settings(max_examples=200, deadline=None)
@given(rec=records, first=rejects, later=st.lists(rejects, min_size=1, max_size=6))
@example(rec=QUEUED, first=REJECT_0_DENORMAL, later=[RejectSignal(0, 1.0, 1.0)])
def test_reject_never_raises_budget(rec, first, later):
    tb = make_budget()
    tb.record(0, rec)
    tb.on_reject(first)
    beta = tb.budget()
    assert not math.isinf(beta)
    for sig in later:
        tb.on_reject(sig)
        assert tb.budget() <= beta
        beta = tb.budget()


@settings(max_examples=200, deadline=None)
@given(rec=records, first=accepts, later=st.lists(accepts, min_size=1, max_size=6))
@example(rec=QUEUED, first=ACCEPT_0_DENORMAL, later=[AcceptSignal(0, 1.0, 1.0)])
def test_accept_never_lowers_budget(rec, first, later):
    tb = make_budget()
    tb.record(0, rec)
    tb.on_accept(first)
    beta = tb.budget()
    assert not math.isinf(beta)
    for sig in later:
        tb.on_accept(sig)
        assert tb.budget() >= beta
        beta = tb.budget()


# --------------------------------------------------------------------- #
# Out-of-order delivery converges (§4.5: min/max against beta_old)       #
# --------------------------------------------------------------------- #
@settings(max_examples=150, deadline=None)
@given(
    pairs=st.lists(st.tuples(records, rejects), min_size=2, max_size=6),
    seed=st.integers(0, 2**16),
)
@example(pairs=[(QUEUED, REJECT_0_DENORMAL), (QUEUED, RejectSignal(0, 1.0, 1.0))], seed=1)
def test_out_of_order_rejects_converge(pairs, seed):
    """Any delivery order of the same reject set yields the same budget."""
    import random

    def final(order):
        tb = make_budget()
        for i, (rec, _) in enumerate(pairs):
            tb.record(i, rec)
        for i in order:
            rec, sig = pairs[i]
            tb.on_reject(RejectSignal(i, sig.epsilon, sig.q_bar))
        return tb.budget()

    order = list(range(len(pairs)))
    expected = final(order)
    random.Random(seed).shuffle(order)
    assert final(order) == expected


@settings(max_examples=150, deadline=None)
@given(
    pairs=st.lists(st.tuples(records, accepts), min_size=2, max_size=6),
    seed=st.integers(0, 2**16),
)
@example(pairs=[(QUEUED, ACCEPT_0_DENORMAL), (QUEUED, AcceptSignal(0, 1.0, 1.0))], seed=1)
def test_out_of_order_accepts_converge(pairs, seed):
    import random

    def final(order):
        tb = make_budget()
        for i, (rec, _) in enumerate(pairs):
            tb.record(i, rec)
        for i in order:
            rec, sig = pairs[i]
            tb.on_accept(AcceptSignal(i, sig.epsilon, sig.xi_bar))
        return tb.budget()

    order = list(range(len(pairs)))
    expected = final(order)
    random.Random(seed).shuffle(order)
    assert final(order) == expected


# --------------------------------------------------------------------- #
# Clock-skew cancellation (§4.6.2)                                       #
# --------------------------------------------------------------------- #
@settings(max_examples=150, deadline=None)
@given(
    sigma=st.floats(-1e4, 1e4, allow_nan=False),
    events=st.lists(
        st.tuples(
            st.floats(0.0, 100.0),   # source arrival a_1 (absolute)
            st.floats(0.0, 5.0),     # upstream time u
            st.floats(0.0, 5.0),     # queuing q
            st.integers(1, 25),      # batch size m
            st.floats(0.0, 10.0),    # signal epsilon
            st.booleans(),           # accept?
        ),
        min_size=1,
        max_size=8,
    ),
)
def test_uniform_clock_skew_cancels(sigma, events):
    """Records and signals are built from *absolute* timestamps exactly the
    way a task computes them (u = arrival - source_arrival, d = u + q + xi);
    shifting every clock by the same sigma leaves all durations — and hence
    every budget trajectory — bit-identical."""

    def run(skew):
        tb = make_budget()
        for i, (a1, u, q, m, eps, is_accept) in enumerate(events):
            a1s = a1 + skew          # source clock reading
            arrival = a1s + u        # this task's (skewed) clock reading
            exec_end = arrival + q + xi(m)
            rec = EventRecord(
                departure=exec_end - a1s, queuing=q, batch_size=m, xi=xi(m)
            )
            tb.record(i, rec)
            if is_accept:
                tb.on_accept(AcceptSignal(i, eps, xi_bar=xi(m)))
            else:
                tb.on_reject(RejectSignal(i, eps, q_bar=q))
        return tb.budget()

    # Equality up to float round-off: the *protocol* cancels sigma exactly
    # (only durations are consumed), but building absolute timestamps first
    # costs an ulp here and there at extreme sigma.
    assert run(sigma) == pytest.approx(run(0.0), rel=1e-6, abs=1e-9)
