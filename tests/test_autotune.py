"""Keep-fraction math + calibration-profile properties (core/autotune.py).

Property-based (hypothesis, or the deterministic shim when absent) checks of
the one keep-count formula every data plane shares, plus the profile
artifact's validation/serialization contract and the pow2-bucket boundary
cases where the batched jax plane must agree with the NumPy reference.
"""

import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import autotune, dataplane
from repro.core.autotune import CalibrationProfile
from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data import synthetic

# ------------------------------------------------------- keep-count formula


@settings(max_examples=60, deadline=None)
@given(n=st.integers(min_value=0, max_value=5000),
       frac=st.floats(min_value=0.001, max_value=100.0),
       floor=st.integers(min_value=1, max_value=256))
def test_floor_always_respected(n, frac, floor):
    keep = autotune.keep_count(n, frac, floor)
    if n == 0:
        assert keep == 0
    else:
        assert min(floor, n) <= keep <= n


@settings(max_examples=60, deadline=None)
@given(n=st.integers(min_value=0, max_value=5000),
       f1=st.floats(min_value=0.001, max_value=100.0),
       f2=st.floats(min_value=0.001, max_value=100.0),
       floor=st.integers(min_value=1, max_value=256))
def test_keep_monotone_in_fraction(n, f1, f2, floor):
    lo, hi = min(f1, f2), max(f1, f2)
    assert (autotune.keep_count(n, lo, floor)
            <= autotune.keep_count(n, hi, floor))


@settings(max_examples=60, deadline=None)
@given(n=st.integers(min_value=0, max_value=4000),
       n_max=st.integers(min_value=0, max_value=4000),
       frac=st.floats(min_value=0.001, max_value=100.0),
       floor=st.integers(min_value=1, max_value=256))
def test_keep_monotone_in_candidates(n, n_max, frac, floor):
    """The static_counts bound argument: keep at n_max bounds keep at n≤n_max."""
    lo, hi = min(n, n_max), max(n, n_max)
    assert (autotune.keep_count(lo, frac, floor)
            <= autotune.keep_count(hi, frac, floor))


@settings(max_examples=40, deadline=None)
@given(floor=st.integers(min_value=1, max_value=128),
       frac=st.floats(min_value=0.001, max_value=100.0))
def test_boundary_candidate_counts(floor, frac):
    """n = 1, n = floor, n = floor ± 1: the floor/fraction crossover edges."""
    assert autotune.keep_count(1, frac, floor) == 1
    assert autotune.keep_count(floor, frac, floor) == floor
    if floor > 1:
        assert autotune.keep_count(floor - 1, frac, floor) == floor - 1
    over = autotune.keep_count(floor + 1, frac, floor)
    assert floor <= over <= floor + 1


@settings(max_examples=30, deadline=None)
@given(p=st.integers(min_value=1, max_value=12),
       qn=st.integers(min_value=1, max_value=6),
       floor=st.integers(min_value=1, max_value=128),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_stage_counts_match_scalar_reference_under_profile(p, qn, floor, seed):
    """dataplane.stage_counts (vectorized) ≡ pipeline's per-pair keep_count
    for per-partition fractions — the cross-module agreement the backend
    parity contract rests on."""
    rng = np.random.default_rng(seed)
    frac = rng.uniform(0.5, 100.0, size=p)
    n_cand = rng.integers(0, 3000, size=(qn, p)).astype(np.int32)
    profile = CalibrationProfile(
        keep_frac=frac, min_keep=floor, recall_target=0.95, seed=0,
        sample_queries=1, rank_corr=np.ones(p), required=frac / 100.0)
    cfg = SquashConfig(min_hamming_keep=floor + 7, hamming_perc=3.0)
    keep, take = dataplane.stage_counts(n_cand, cfg, k=10, profile=profile)
    cap = int(np.ceil(cfg.refine_ratio * 10))
    for qi in range(qn):
        for pid in range(p):
            ref = autotune.keep_count(int(n_cand[qi, pid]), frac[pid],
                                      max(floor, 10))
            assert keep[qi, pid] == ref
            assert take[qi, pid] == min(cap, ref)
    keep_s, take_s = dataplane.static_counts(int(n_cand.max()), cfg, k=10,
                                             profile=profile)
    assert (keep <= keep_s).all() and (take <= take_s).all()


def test_stage_counts_profile_none_matches_config():
    """profile=None must reproduce the static-knob formulas, the floor
    raised to k."""
    cfg = SquashConfig(min_hamming_keep=8, hamming_perc=10.0)
    n_cand = np.array([[0, 1, 7, 8, 50, 500, 3000]], dtype=np.int32)
    keep, take = dataplane.stage_counts(n_cand, cfg, k=10)
    for i, n in enumerate(n_cand[0]):
        assert keep[0, i] == autotune.keep_count(
            int(n), cfg.hamming_perc, max(cfg.min_hamming_keep, 10))


# --------------------------------------------------------- profile artifact


def test_profile_validation():
    ones = np.ones(3)
    with pytest.raises(ValueError, match="keep_frac"):
        CalibrationProfile(keep_frac=np.array([0.0, 50.0, 10.0]), min_keep=4,
                           recall_target=0.9, seed=0, sample_queries=8,
                           rank_corr=ones, required=ones)
    with pytest.raises(ValueError, match="keep_frac"):
        CalibrationProfile(keep_frac=np.array([101.0]), min_keep=4,
                           recall_target=0.9, seed=0, sample_queries=8,
                           rank_corr=ones[:1], required=ones[:1])
    with pytest.raises(ValueError, match="min_keep"):
        CalibrationProfile(keep_frac=np.array([10.0]), min_keep=0,
                           recall_target=0.9, seed=0, sample_queries=8,
                           rank_corr=ones[:1], required=ones[:1])


def test_profile_json_round_trip():
    prof = CalibrationProfile(
        keep_frac=np.array([12.5, 3.25, 100.0]), min_keep=40,
        recall_target=0.95, seed=17, sample_queries=64,
        rank_corr=np.array([0.9, 0.5, 0.7]),
        required=np.array([0.1, 0.02, 0.9]))
    back = CalibrationProfile.from_dict(json.loads(json.dumps(prof.to_dict())))
    np.testing.assert_array_equal(back.keep_frac, prof.keep_frac)
    np.testing.assert_array_equal(back.rank_corr, prof.rank_corr)
    np.testing.assert_array_equal(back.required, prof.required)
    assert back.min_keep == prof.min_keep
    assert back.recall_target == prof.recall_target
    assert back.seed == prof.seed and back.sample_queries == prof.sample_queries


def test_spearman_basics():
    x = np.arange(10.0)
    assert autotune.spearman(x, x) == pytest.approx(1.0)
    assert autotune.spearman(x, -x) == pytest.approx(-1.0)
    assert autotune.spearman(np.ones(5), x[:5]) == pytest.approx(1.0)


# ------------------------------------------- calibration + plane integration


@pytest.fixture(scope="module")
def tuned_index():
    ds = synthetic.make_vector_dataset("sift1m", scale=0.006, num_queries=16,
                                       seed=11)
    preds = synthetic.default_predicates(ds.attr_cardinality)
    cfg = SquashConfig(num_partitions=5, kmeans_iters=4, lloyd_iters=6)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=11)
    profile = index.autotune(recall_target=0.95, sample=32, seed=3)
    return ds, preds, index, profile


def test_calibration_deterministic(tuned_index):
    ds, _, index, profile = tuned_index
    again = autotune.calibrate(index, recall_target=0.95, sample=32, seed=3)
    np.testing.assert_array_equal(profile.keep_frac, again.keep_frac)
    np.testing.assert_array_equal(profile.rank_corr, again.rank_corr)
    assert profile.min_keep == again.min_keep


def test_set_profile_validates_partition_count(tuned_index):
    _, _, index, profile = tuned_index
    bad = CalibrationProfile.from_dict(profile.to_dict())
    bad.keep_frac = bad.keep_frac[:-1]
    with pytest.raises(ValueError, match="partitions"):
        index.set_profile(bad)
    index.set_profile(profile)  # restore


def test_pow2_bucket_boundaries_backend_parity(tuned_index):
    """Query counts on and just past the pow2 bucket edges (1, 2, 3, 4, 5,
    8, 9) must keep numpy/jax ids bitwise-identical under the profile."""
    ds, preds, index, _ = tuned_index
    for qn in (1, 2, 3, 4, 5, 8, 9):
        q = ds.queries[:qn]
        ids_n, _, s_n = index.search(q, preds, k=7, backend="numpy")
        ids_j, _, s_j = index.search(q, preds, k=7, backend="jax")
        np.testing.assert_array_equal(ids_n, ids_j)
        assert s_n == s_j


def test_profile_changes_plane_key_not_correctness(tuned_index):
    """Installing/clearing a profile flushes the jitted-plane cache (static
    keep shapes change) and flips stats between tuned and static budgets."""
    ds, preds, index, profile = tuned_index
    _, _, s_tuned = index.search(ds.queries, preds, k=10, backend="jax")
    index.set_profile(None)
    try:
        _, _, s_static = index.search(ds.queries, preds, k=10, backend="jax")
    finally:
        index.set_profile(profile)
    assert s_tuned.hamming_in == s_static.hamming_in
    assert s_tuned.hamming_kept != s_static.hamming_kept
