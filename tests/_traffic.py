"""The benchmark cells' traffic at test size, and the guarantees an answer keeps.

Each request of the cells (``bench/traffic/``) draws four range predicates,
one per attribute, each ``width`` values wide of the attribute's cardinality
of 16, at a random lower end. :func:`cell_predicates` builds them the same
way from a seed; :func:`assert_guarantees` checks what every served answer
must hold whatever the width and k.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.core.attributes import Predicate, ground_truth_mask

CARDINALITY = 16
NUM_ATTRIBUTES = 4


def cell_predicates(width: int, seed: int) -> List[Predicate]:
    """Four ``B`` (between) predicates, one per attribute, ``width`` of 16
    values wide, their lower ends drawn from ``seed``."""
    lows = np.random.default_rng(seed).integers(
        0, CARDINALITY - width + 1, size=NUM_ATTRIBUTES)
    return [Predicate(attr=a, op="B", lo=float(lo), hi=float(lo + width - 1))
            for a, lo in enumerate(lows)]


def assert_guarantees(ids: np.ndarray, attributes: np.ndarray,
                      predicates, k: int,
                      live: Optional[np.ndarray] = None) -> None:
    """No returned id fails a predicate (or is dead, given ``live``), none
    repeats in its row, and no slot is ``-1`` while at least k rows pass."""
    passing = ground_truth_mask(attributes, predicates)
    if live is not None:
        passing &= live
    enough = int(passing.sum()) >= k
    assert ids.shape[1] == k
    for row in ids:
        got = row[row >= 0]
        assert passing[got].all(), "a returned id fails the filter"
        assert np.unique(got).size == got.size, "an id repeats in a row"
        assert (row[got.size:] == -1).all(), "-1 before a returned id"
        if enough:
            assert got.size == k, "an empty slot while at least k rows pass"
