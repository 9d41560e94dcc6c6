"""The seeded generators: deterministic, and shaped as they say."""

import json
import os

import numpy as np
import pytest

from perf import generators

HERE = os.path.dirname(os.path.abspath(__file__))


def _ml20m():
    with open(os.path.join(HERE, "..", "configs",
                           "mfsgd-ml20m-x4-r64.json")) as fh:
        return json.load(fh)["data"]


def test_degree_sequence_meets_ends_and_total():
    d = generators.lognormal_degrees(138_493, 20_000_263, 20, 9254, 68)
    assert d.sum() == 20_000_263
    assert d.min() == 20 and d.max() == 9254
    assert (np.diff(d[1:-1]) <= 1).all()  # descending but for the +1 tail
    assert abs(np.median(d) - 68) <= 2


def test_degree_sequence_refuses_the_impossible():
    with pytest.raises(ValueError):
        generators.lognormal_degrees(100, 50, 1, 10, 2)  # total < n * dmin


@pytest.mark.parametrize("scale", [0.02])
def test_skewed_ratings_marginals_and_determinism(scale):
    """The source's shape at 1/50 of its rows (the full 20M-rating draw
    is the same code; its marginals are checked in
    test_full_size_marginals)."""
    data = dict(_ml20m())
    data.update(n_users=int(data["n_users"] * scale),
                nnz=int(data["nnz"] * scale),
                item_max=int(data["item_max"] * scale))
    u, i, v = generators.skewed_ratings(data, seed=7)
    u2, i2, v2 = generators.skewed_ratings(data, seed=7)
    assert (u == u2).all() and (i == i2).all() and (v == v2).all()
    u3, i3, _ = generators.skewed_ratings(data, seed=8)
    assert not (i == i3).all()
    assert len(u) == len(i) == len(v) == data["nnz"]
    assert u.dtype == np.int32 and i.dtype == np.int32
    assert v.dtype == np.float32
    per_user = np.bincount(u, minlength=data["n_users"])
    per_item = np.bincount(i, minlength=data["n_items"])
    # the user side is exact; the item side is drawn, so it is met to
    # about the square root of each degree
    assert per_user.min() == data["user_min"]
    assert per_user.max() == data["user_max"]
    assert abs(per_item.max() - data["item_max"]) <= 0.1 * data["item_max"]
    assert abs(np.median(per_item) - data["item_median"]) <= 3
    assert (np.diff(u) >= 0).all()  # user-major, as the source's files
    # weight does not follow id: the heaviest user moves with the seed
    assert per_user.argmax() != np.bincount(u3).argmax()
    # values: a rank-8 truth of unit scale plus 0.1 noise
    assert 0.2 < v.std() < 0.6


def test_full_size_marginals():
    """The size the cell runs, and the source's own: exact nnz, every
    user at least 20 ratings, the heaviest user and item within 10% of
    the README's (the item's scaled with the ratings)."""
    data = _ml20m()
    scale = data["nnz"] / 20_000_263
    assert data["n_users"] == round(138_493 * scale)
    for d in (data, dict(data, n_users=138_493, nnz=20_000_263,
                         item_max=67_310)):
        du = generators.lognormal_degrees(
            d["n_users"], d["nnz"], d["user_min"], d["user_max"],
            d["user_median"])
        di = generators.lognormal_degrees(
            d["n_items"], d["nnz"], d["item_min"], d["item_max"],
            d["item_median"])
        assert du.sum() == di.sum() == d["nnz"]
        assert du.min() >= 20
        assert abs(du.max() - 9254) <= 0.1 * 9254
        assert abs(di.max() - 67_310 * d["nnz"] / 20_000_263) \
            <= 0.1 * di.max()


def test_uniform_ratings_shape():
    data = dict(_ml20m(), n_users=500, n_items=200, nnz=10_000)
    u, i, v = generators.uniform_ratings(data, seed=1)
    assert len(u) == 10_000 and u.max() < 500 and i.max() < 200
    assert v.dtype == np.float32


def test_host_points_do_not_depend_on_thread_count(monkeypatch):
    a = generators.normal_points_host(1000, 7, seed=3)
    b = generators.normal_points_host(1000, 7, seed=3)
    assert a.dtype == np.float32 and a.shape == (1000, 7)
    assert (a == b).all()
    assert abs(a.mean()) < 0.05 and abs(a.std() - 1) < 0.05
    assert not (a == generators.normal_points_host(1000, 7, seed=4)).all()


def test_device_points_are_seeded_and_sharded():
    import jax

    devs = jax.devices()[:2]
    a = generators.normal_points_device(64, 5, 11, devs)
    b = generators.normal_points_device(64, 5, 11, devs)
    assert a.shape == (128, 5)
    assert (np.asarray(a) == np.asarray(b)).all()
    assert len(a.addressable_shards) == 2
    # the two bands differ: each has its own key
    assert not (np.asarray(a)[:64] == np.asarray(a)[64:]).all()
