"""The benchmark's generators: the dense copy equals the program's bit for
bit, and the sparse rows hold their published number of distinct
nonzeros."""
import numpy as np
import pytest

from chipbench import gen, run
from repro.data import synthetic


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_017])
def test_dense_matches_program(seed):
    X1, y1 = gen.make_dense_classification(512, 28, seed=seed)
    X2, y2 = synthetic.make_dense_classification(512, 28, seed=seed)
    assert X1.dtype == X2.dtype and np.array_equal(X1, X2)
    assert np.array_equal(y1, y2)


@pytest.mark.parametrize("skew", [0.0, 1.1])
def test_one_distinct_id_a_field(skew):
    d, fields = 100_003, 39
    (idx, val), y = gen.make_field_classification(
        4096, d, fields=fields, seed=3_000_000_017, skew=skew, w_seed=1)
    bounds = gen.field_bounds(d, fields)
    assert idx.shape == val.shape == (4096, fields) and idx.dtype == np.int32
    assert np.all(idx >= bounds[:-1]) and np.all(idx < bounds[1:])
    assert np.all(np.diff(np.sort(idx, axis=1), axis=1) > 0)
    assert np.count_nonzero(val) == val.size
    assert set(np.unique(y)) == {-1.0, 1.0}


def test_skew_makes_a_field_s_first_ids_popular():
    (idx, _), _ = gen.make_field_classification(
        65536, 1_000_000, fields=39, seed=5, skew=1.1, w_seed=1)
    first = gen.field_bounds(1_000_000, 39)[:-1]
    share = np.mean(idx == first)
    # Zipf(1.1) over 25,641 ids puts about 14% of a field's rows on its
    # first id
    assert 0.12 < share < 0.16


def test_criteo_rows_hold_39_nonzeros_padded_to_40():
    cfg = run.load_cell("criteo-1chip")["config"]
    data = gen.make_data(cfg, 2048, seed=3_000_000_017)
    assert data["idx"].shape == (2048, 40) == data["val"].shape
    assert not data["val"][:, 39:].any()
    nonzeros = np.count_nonzero(data["val"], axis=1)
    assert nonzeros.mean() == 39 == nonzeros.min()
    real = data["idx"][:, :39]
    assert np.all(np.diff(np.sort(real, axis=1), axis=1) > 0)


def test_same_seed_same_inputs():
    cfg = run.load_cell("criteo-1chip")["config"]
    a, b = (gen.make_data(cfg, 512, seed=2_200_000_011) for _ in range(2))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    c = gen.make_data(cfg, 512, seed=2_200_000_012)
    assert not np.array_equal(a["idx"], c["idx"])


def test_fixed_labelling_model():
    """With `w_seed`, the rows still come from `seed` alone and the
    labelling weights from `w_seed` alone."""
    a = gen.make_field_classification(256, 4096, fields=39, seed=3,
                                      skew=1.1, w_seed=1)
    b = gen.make_field_classification(256, 4096, fields=39, seed=3,
                                      skew=1.1)
    assert np.array_equal(a[0][0], b[0][0]) and np.array_equal(a[0][1],
                                                                b[0][1])
    c = gen.make_dense_classification(64, 28, seed=3, w_seed=2)
    d = gen.make_dense_classification(64, 28, seed=3)
    assert np.array_equal(c[0], d[0])
    e = gen.make_dense_classification(64, 28, seed=3, w_seed=2)
    assert np.array_equal(c[1], e[1])
