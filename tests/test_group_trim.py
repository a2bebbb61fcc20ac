"""The per-server trim selects, it does not sort (PR 40):
``results.trim_group_candidates`` against the implementation it replaced,
kept here verbatim as the oracle (a stable ``argsort`` of every group), index
for index wherever the groups tied at a boundary number at most
``MAX_TRIM_TIES``; above the cap, the contract its comment states; and one
case that fails if the whole state is ordered again."""
from typing import List

import numpy as np
import pytest

from pinot_tpu.engine import results
from pinot_tpu.engine.results import MAX_TRIM_TIES, trim_group_candidates

DIRECTIONS = {  # ascending flags an aggregate: one, two, three aggregates
    "asc": [True], "desc": [False], "asc_desc": [True, False], "desc_asc_desc": [False, True, False],
}
K_OF = {"trim": lambda trim: trim, "trim_plus_1": lambda trim: trim + 1, "2000": lambda trim: 2_000,
        "220000": lambda trim: 220_000}


def parents_trim(order_vals_list: List[np.ndarray], ascending_list: List[bool], top_n: int, k: int) -> np.ndarray:
    """``trim_group_candidates`` as PR 39 had it."""
    trim = max(top_n * 5, 100)
    if k <= trim:
        return np.arange(k)
    candidates: set = set()
    for ov, asc in zip(order_vals_list, ascending_list):
        order = np.argsort(ov, kind="stable")
        chosen = order[:trim] if asc else order[-trim:]
        candidates.update(chosen.tolist())
        boundary = ov[order[trim - 1 if asc else -trim]]
        ties = np.nonzero(ov == boundary)[0]
        if ties.size > MAX_TRIM_TIES:
            ties = ties[:MAX_TRIM_TIES]
        candidates.update(ties.tolist())
    return np.asarray(sorted(candidates), dtype=np.int64)


def distinct(rng, k: int, trim: int) -> np.ndarray:
    return rng.permutation(k).astype(np.float32)


def boundary_ties(rng, k: int, trim: int) -> np.ndarray:
    """Both cuts fall inside a run of equal values: a tenth of the
    groups (under the cap) at each end's ``trim``-th value."""
    ov = rng.permutation(k).astype(np.float32)
    run = max(2, min(k // 10, MAX_TRIM_TIES // 2))
    order = np.argsort(ov)
    ov[order[trim - 1: trim - 1 + run]] = ov[order[trim - 1]]
    ov[order[k - trim - run + 1: k - trim + 1]] = ov[order[k - trim]]
    return ov


def few_values(rng, k: int, trim: int) -> np.ndarray:
    """A count of 1 to 22 rows: every value heavily tied, the boundary's
    run under the cap at 2,000 groups and over it at 220,000."""
    return rng.integers(1, 23, k).astype(np.int64)


def all_equal(rng, k: int, trim: int) -> np.ndarray:
    return np.full(k, 7.5, dtype=np.float32)


def not_finite(rng, k: int, trim: int) -> np.ndarray:
    """NaN, +inf and -inf, fewer of each than the trim, among distinct values."""
    ov = rng.permutation(k).astype(np.float32)
    n = min(trim // 3, k // 8)
    at = rng.choice(k, 3 * n, replace=False)
    ov[at[:n]], ov[at[n: 2 * n]], ov[at[2 * n:]] = np.nan, np.inf, -np.inf
    return ov


def mostly_nan(rng, k: int, trim: int) -> np.ndarray:
    """Three groups of four hold NaN, which sorts last and ties with
    nothing: the boundary itself ascending at ``trim + 1`` groups (fewer
    numbers than the trim) and descending at 2,000 and at 220,000."""
    ov = rng.permutation(k).astype(np.float32)
    ov[rng.permutation(k)[k // 4:]] = np.nan
    return ov


VALUES = {f.__name__: f for f in (distinct, boundary_ties, few_values, all_equal, not_finite, mostly_nan)}


def tied_at_the_cut(ov: np.ndarray, asc: bool, trim: int) -> int:
    boundary = np.sort(ov)[trim - 1 if asc else -trim]
    return int(np.count_nonzero(ov == boundary))


def holds_the_contract_above_the_cap(order_vals, ascending, top_n: int, k: int, keep: np.ndarray) -> None:
    trim = max(top_n * 5, 100)
    assert keep.dtype == np.int64 and np.all(np.diff(keep) > 0) and 0 <= keep[0] and keep[-1] < k
    assert np.array_equal(keep, trim_group_candidates(order_vals, ascending, top_n, k))
    kept = np.zeros(k, dtype=bool)
    kept[keep] = True
    union = np.zeros(k, dtype=bool)
    for ov, asc in zip(order_vals, ascending):
        boundary = np.sort(ov)[trim - 1 if asc else -trim]
        beyond = ov < boundary if asc else ov > boundary
        tied = np.nonzero(ov == boundary)[0]
        assert kept[beyond].all()
        # the tied, in ascending index, until both the cap and the trim are met
        want = tied[: max(MAX_TRIM_TIES, trim - int(beyond.sum()))]
        assert kept[want].all()
        mine = np.count_nonzero(beyond) + want.size
        assert trim <= mine <= trim + MAX_TRIM_TIES
        union[beyond] = True
        union[want] = True
    assert np.array_equal(kept, union)


@pytest.mark.parametrize("seed", [0, 40])
@pytest.mark.parametrize("values", sorted(VALUES))
@pytest.mark.parametrize("k_name", list(K_OF))
@pytest.mark.parametrize("top_n", [1, 10, 50])
@pytest.mark.parametrize("directions", sorted(DIRECTIONS))
def test_the_selection_keeps_what_the_sort_kept(directions, top_n, k_name, values, seed):
    ascending = DIRECTIONS[directions]
    trim = max(top_n * 5, 100)
    k = K_OF[k_name](trim)
    rng = np.random.default_rng([seed, top_n, k, len(ascending)])
    order_vals = [VALUES[values](rng, k, trim) for _ in ascending]
    keep = trim_group_candidates(order_vals, ascending, top_n, k)
    if k > trim and any(tied_at_the_cut(ov, asc, trim) > MAX_TRIM_TIES for ov, asc in zip(order_vals, ascending)):
        holds_the_contract_above_the_cap(order_vals, ascending, top_n, k, keep)
        return
    want = parents_trim(order_vals, ascending, top_n, k)
    assert keep.dtype == want.dtype and np.array_equal(keep, want)


@pytest.mark.parametrize("asc", [True, False], ids=["asc", "desc"])
def test_a_trim_wider_than_the_cap_is_still_met(asc):
    """TOP 2,400 trims to 12,000: of one tied run the first 12,000 by index."""
    k, top_n = 30_000, 2_400
    ov = np.ones(k, dtype=np.int64)
    ov[:7] = 0 if asc else 2
    keep = trim_group_candidates([ov], [asc], top_n, k)
    assert np.array_equal(keep, np.arange(12_000))
    holds_the_contract_above_the_cap([ov], [asc], top_n, k, keep)


@pytest.mark.parametrize("values", ["distinct", "few_values"])
def test_the_whole_state_is_not_sorted_again(monkeypatch, values):
    k, top_n = 220_000, 1
    limit = max(top_n * 5, 100) + MAX_TRIM_TIES
    rng = np.random.default_rng(15)
    order_vals = [VALUES[values](rng, k, 100) for _ in range(2)]
    want = trim_group_candidates(order_vals, [False, True], top_n, k)

    def refusing(real):
        def call(a, *args, **kwargs):
            assert np.size(a) <= limit, f"np.{real.__name__} of {np.size(a)} values inside the trim"
            return real(a, *args, **kwargs)
        return call

    for name in ("argsort", "sort", "lexsort", "unique"):
        monkeypatch.setattr(np, name, refusing(getattr(np, name)))
    monkeypatch.setattr(results, "sorted", lambda *a, **k: pytest.fail("sorted() inside the trim"), raising=False)
    keep = trim_group_candidates(order_vals, [False, True], top_n, k)
    monkeypatch.undo()
    assert np.array_equal(keep, want)
    monkeypatch.setattr(np, "argsort", refusing(np.argsort))
    with pytest.raises(AssertionError, match="inside the trim"):  # the patch does catch the sort
        parents_trim(order_vals, [False, True], top_n, k)
