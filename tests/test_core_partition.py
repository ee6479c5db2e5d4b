"""Partition-space invariants (the Table VII analogue)."""
import pytest

from repro.core.partition import (
    CHIPS_PER_UNIT,
    N_UNITS,
    Partition,
    Slice,
    enumerate_partitions,
    partitions_by_arity,
    slice_label,
    solo_partition,
)


def test_table_covers_all_arities():
    by = partitions_by_arity(4)
    assert set(by) == {1, 2, 3, 4}
    assert all(len(v) >= 1 for v in by.values())


def test_partitions_respect_cmax():
    for c_max in (1, 2, 3, 4):
        assert all(p.arity <= c_max for p in enumerate_partitions(c_max))


def test_slice_invariants():
    for p in enumerate_partitions(4):
        assert p.total_units <= N_UNITS, p.label
        for s in p.slices:
            assert s.units in (1, 2, 4, 8)
            assert sum(s.shares) <= 1.0 + 1e-9, p.label
            assert s.chips == s.units * CHIPS_PER_UNIT
        assert len(p.slots) == p.arity


def test_torus_factor_only_full_pod():
    full = Slice(8, (1.0,))
    half = Slice(4, (1.0,))
    assert full.torus_factor == 1.0
    assert half.torus_factor == 0.5


def test_styles_partition_the_table():
    styles = {p.style for p in enumerate_partitions(4)}
    assert styles == {"solo", "mps", "mig", "hier"}


def test_action_space_size_matches_paper_scale():
    """W + N_p should land near the paper's A = 29 output head."""
    n_p = len(enumerate_partitions(4))
    assert 15 <= n_p <= 25, n_p          # paper: 17
    assert 25 <= 12 + n_p <= 37          # paper: 29


def test_invalid_slices_rejected():
    with pytest.raises(AssertionError):
        Slice(3, (1.0,))                  # non-power-of-two width
    with pytest.raises(AssertionError):
        Slice(4, (0.7, 0.6))              # shares exceed 1


# the table's order and labels: the DQN's action indices point into them
TABLE_LABELS = [
    "[{1.0},1m]",
    "[(0.1)+(0.9),1m]", "[(0.2)+(0.8),1m]", "[(0.3)+(0.7),1m]",
    "[(0.4)+(0.6),1m]", "[(0.5)+(0.5),1m]", "[{.5},.5m]+[{.5},.5m]",
    "[(.1)+(.1)+(.8),1m]", "[(.2)+(.2)+(.6),1m]", "[(.2)+(.3)+(.5),1m]",
    "[(.33)+(.33)+(.34),1m]", "[{.5},.5m]+[(.5)+(.5),{.5},.5m]",
    "[{.5},.5m]+[(.25)+(.75),{.5},.5m]",
    "[{.5},.5m]+[{.25},.25m]+[{.25},.25m]",
    "[(.1)+(.1)+(.1)+(.7),1m]", "[(.25)x4,1m]", "[(.1)+(.2)+(.3)+(.4),1m]",
    "[(.5)+(.5),{.5},.5m]x2", "[(.25)+(.75),{.5},.5m]x2",
    "[(.5)+(.5),{.5},.5m]+[{.25},.25m]x2", "[{.25},.25m]x4",
]


def test_solo_partition_is_the_tables_first_entry_built_once():
    solo = solo_partition()
    assert solo == enumerate_partitions(1)[0]
    assert solo is enumerate_partitions(4)[0]
    assert solo_partition() is solo and solo_partition(N_UNITS) is solo
    assert solo.label == "[{1.0},1m]"
    assert solo.slices == (Slice(N_UNITS, (1.0,)),)


@pytest.mark.parametrize("units", [1, 2, 4])
def test_narrow_solo_partitions_unchanged(units):
    s = Slice(units, (1.0,))
    assert solo_partition(units) == Partition((s,), slice_label((s,)))
    assert solo_partition(units).label == {1: "[{1},.125m]", 2: "[{1},.25m]",
                                           4: "[{1},.5m]"}[units]
    assert solo_partition(units) is solo_partition(units)


def test_solo_partition_refuses_an_invalid_width():
    with pytest.raises(AssertionError):
        solo_partition(3)


@pytest.mark.parametrize("c_max", [1, 2, 3, 4])
def test_enumerate_partitions_returns_a_fresh_list(c_max):
    want = [p.label for p in enumerate_partitions(4) if p.arity <= c_max]
    first = enumerate_partitions(c_max)
    assert [p.label for p in first] == want
    first.append(solo_partition(2))
    first.reverse()
    again = enumerate_partitions(c_max)
    assert again is not first
    assert [p.label for p in again] == want
    assert [p.label for p in enumerate_partitions(4)] == TABLE_LABELS
