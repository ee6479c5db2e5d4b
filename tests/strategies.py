"""Shared randomized generators + parity helpers for the property suites.

One place owns the shapes the heap-vs-vectorized parity tests range over:

* **Trace specs** — every :data:`TRACE_FAMILIES` family x size x seed x
  load, built through one :func:`make_trace` so fleet suites can scale
  the rate to their capacity.
* **Adversarial traces** — same-instant bursts of *duplicate-tenant*
  submissions (one binary popped several times into one window), the
  shape that pins pop-order tie-breaking and the name-keyed FIFO record
  attribution of ``_form_window``.
* **Job profiles** — zoo rows with and without the ``meta["units"]``
  placement hint (``JobProfile.requested_units``).
* **Fleet topologies** — pod-width tuples led by the mandatory
  full-width pod; **engine knobs** — (window, backfill) pairs.
* **Parity assertions** — :func:`close` (f32-device vs f64-heap
  tolerance) and :func:`assert_parity` (decision-level equality), thin
  wrappers over the library's ``times_close``/``decision_diffs`` (which
  ``chip_smoke.py`` also uses), shared by ``test_vecsim.py``,
  ``test_fleet.py``, and ``test_parity_fuzz.py``.

Import through the same hypothesis-or-shim seam as the test modules; the
generators only use the surface ``_hypothesis_compat`` implements
(``composite``/``tuples``/``sampled_from``/scalars), so the suite runs
with or without the real package.
"""
import dataclasses

try:
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_compat import st

from repro.core import make_zoo
from repro.core.partition import N_UNITS
from repro.online import Arrival, TRACE_FAMILIES
from repro.online.simulator import decision_diffs, times_close

ZOO = make_zoo()

FAMILIES = tuple(sorted(TRACE_FAMILIES))
HINT_WIDTHS = (1, 2, 4, 8)


def make_trace(fam: str, n: int, seed: int, load: float,
               capacity: float = 1.0) -> list:
    """The one trace constructor the suites share (fleet tests pass the
    fleet's full-pod-equivalent ``capacity`` so nominal load is
    comparable across topologies)."""
    return TRACE_FAMILIES[fam](ZOO, n=n, load=load, seed=seed,
                               capacity=capacity)


# ------------------------------------------------------------- strategies

def trace_specs(max_n: int = 60, families=FAMILIES):
    """(family, n, seed, load) — the argument tuple of :func:`make_trace`."""
    return st.tuples(st.sampled_from(families),
                     st.integers(5, max_n),
                     st.integers(0, 50),
                     st.floats(min_value=0.5, max_value=1.8))


@st.composite
def job_profiles(draw, units_hint=None):
    """A zoo profile, optionally re-keyed with a ``meta["units"]`` request.

    ``units_hint=None`` draws the presence of the hint too; hinted
    variants get the ``@u{w}`` name/binary suffix the fragmented family
    uses, so the profile repository sees a distinct application per
    requested width.
    """
    j = draw(st.sampled_from(ZOO))
    hinted = draw(st.booleans()) if units_hint is None else units_hint
    if not hinted:
        return j
    w = draw(st.sampled_from(HINT_WIDTHS))
    return dataclasses.replace(j, name=f"{j.name}@u{w}",
                               meta={**j.meta, "units": w})


@st.composite
def adversarial_traces(draw, max_bursts: int = 5):
    """Same-instant duplicate-tenant bursts.

    Each burst submits one binary 2-4 times at one timestamp (plus an
    optional hinted bystander), so a single dispatch window holds several
    pops of the same name: the shape that distinguishes row-identity
    attribution from the heap's name-keyed FIFO, and that exercises
    same-instant pop ordering.  Inter-burst gaps are drawn wide enough
    that bursts can also pile into one window under load.
    """
    out, t = [], 0.0
    for _ in range(draw(st.integers(2, max_bursts))):
        t += draw(st.floats(min_value=0.0, max_value=400.0))
        dup = draw(job_profiles(units_hint=False))
        for _ in range(draw(st.integers(2, 4))):
            out.append(Arrival(t=t, binary=f"bin://{dup.name}", profile=dup))
        if draw(st.booleans()):
            by = draw(job_profiles(units_hint=True))
            out.append(Arrival(t=t, binary=f"bin://{by.name}", profile=by))
    return out


@st.composite
def fleet_topologies(draw, max_pods: int = 4):
    """Pod-width tuples; ``SimConfig`` requires one full-width pod."""
    n_extra = draw(st.integers(0, max_pods - 1))
    extra = tuple(draw(st.sampled_from((2, 4, 8))) for _ in range(n_extra))
    return (N_UNITS, *extra)


def engine_knobs():
    """(window, backfill) — the formation-seam knobs both engines share."""
    return st.tuples(st.sampled_from((2, 4, 8)), st.booleans())


# ------------------------------------------------------ parity assertions

close = times_close


def assert_parity(h, v):
    """Decision-level equality + f32-resolution times between engines."""
    diffs = decision_diffs(h, v)
    assert not diffs, f"{len(diffs)} differences: {diffs[:10]}"
