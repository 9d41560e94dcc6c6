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


def _cut(scale):
    """The cell's data block with its rows cut to ``scale``."""
    data = _ml20m()
    data.update(n_users=int(data["n_users"] * scale),
                nnz=int(data["nnz"] * scale),
                item_max=int(data["item_max"] * scale))
    return data


def _without_id_seed(data):
    return {k: v for k, v in data.items() if k != "id_seed"}


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
    test_full_size_marginals).  Without ``id_seed``: the seed deals
    the ids too, as it did for every configuration before PR 28."""
    data = _without_id_seed(_cut(scale))
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


# -- id_seed: which ids are heavy is the data set's, not the run's ----------

def chunk_steps(users, items, n_users, n_items, entry_cap=2048):
    """Chunk steps an epoch of the pallas layout on one worker, counted
    from tile ids alone with the program's own bounds and tiles: a tile of
    ``count`` ratings is cut into entries of ``C`` and each entry into the
    512-wide chunks that hold its ratings; an epoch runs as many steps in
    every half-slice as the fuller one has (``set_ratings`` stages
    ``[slices, max, 512]``).  Also returns the count of each half-slice."""
    from harp_tpu.models import mfsgd
    from harp_tpu.ops import mfsgd_kernel

    cfg = mfsgd.MFSGDConfig(algo="pallas", entry_cap=entry_cap)
    u_tile, i_tile = mfsgd.tiles(cfg)
    ns = mfsgd.rotate_chunks_resolved(cfg)
    _, i_own, u_bound, ib2 = mfsgd._dense_bounds(
        n_users, n_items, 1, ns, u_tile, i_tile)
    ntu, nti = u_bound // u_tile, ib2 // i_tile
    sid = items // i_own
    gtile = ((sid.astype(np.int64) * ntu + users // u_tile) * nti
             + (items - sid * i_own) // i_tile)
    counts = np.bincount(gtile, minlength=ns * ntu * nti).reshape(
        ns, ntu, nti)
    c = int(min(entry_cap, max(8, 8 * -(-int(counts.max()) // 8))))
    lane = mfsgd_kernel._LANE
    cc = 512 if c > 512 else lane * -(-c // lane)
    full, rest = np.divmod(counts, c)
    chunks = full * -(-c // cc) + -(-rest // cc)
    # a W block without a rating still runs one no-op chunk
    per_slice = chunks.sum((1, 2)) + (counts.sum(2) == 0).sum(1)
    return ns * int(per_slice.max()), per_slice.tolist()


def test_the_cell_pins_its_heavy_ids():
    assert isinstance(_ml20m()["id_seed"], int)


def test_id_seed_pins_the_heavy_ids_and_the_seed_draws_the_ratings():
    data = _cut(0.02)
    u, i, v = generators.skewed_ratings(data, seed=7)
    u2, i2, v2 = generators.skewed_ratings(data, seed=8)
    # the user side is exact: the same user holds the same degree
    assert (u == u2).all()
    # the item side is drawn: the same ids are the heavy ones
    n = data["n_items"]
    top, top2 = (set(np.argsort(np.bincount(x, minlength=n))[-20:])
                 for x in (i, i2))
    assert len(top & top2) >= 18
    # and every rating's item and value come from the seed
    assert (i != i2).mean() > 0.9 and (v != v2).mean() > 0.99
    # another id_seed is another data set
    u3, i3, _ = generators.skewed_ratings(
        dict(data, id_seed=data["id_seed"] + 1), seed=7)
    assert not (u == u3).all()
    assert np.bincount(i).argmax() != np.bincount(i3).argmax()
    # same seed, same data
    again = generators.skewed_ratings(data, seed=7)
    assert all((a == b).all() for a, b in zip((u, i, v), again))


WITHOUT_ID_SEED_SHA256 = (
    "a2add9b469d293203391ddceb189d67bce81d8d21a556d240aed752e9418c1b0")


def test_without_id_seed_the_outputs_are_the_old_ones_to_the_bit():
    """Hash of the three arrays as the generator of PR 22..26 made them
    (recorded from ``git show b46f8bb:perf/generators.py``, PR 28)."""
    import hashlib

    data = _without_id_seed(_cut(0.005))
    h = hashlib.sha256()
    for a in generators.skewed_ratings(data, seed=7):
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == WITHOUT_ID_SEED_SHA256


def test_chunk_count_is_the_programs():
    """The count from tile ids alone is the chunk list ``set_ratings``
    stages (``partition_ratings_tiles`` + ``insert_coverage_entries``)."""
    from harp_tpu.models import mfsgd
    from harp_tpu.ops import mfsgd_kernel

    data = _cut(0.005)
    u, i, v = generators.skewed_ratings(data, seed=3)
    steps, per_slice = chunk_steps(u, i, data["n_users"], data["n_items"])
    eu, ei, ev, ou, oi, _, _, ub, _ = mfsgd.partition_ratings_tiles(
        u, i, v, data["n_users"], data["n_items"], 1, 256, 256, 2048)
    cu, _, _, _ = mfsgd_kernel.insert_coverage_entries(
        eu, ei, ev, ou, oi, ub, 256, 256)
    assert cu.shape[2] == 512
    assert steps == cu.shape[0] * cu.shape[1] == 2 * max(per_slice)


SEEDS = (13, 301, 302, 303, 304)
PINNED_RANGE, LOOSE_RANGE = 0.008, 0.025


def _step_range(data):
    steps = [chunk_steps(*generators.skewed_ratings(data, seed=s)[:2],
                         data["n_users"], data["n_items"])[0]
             for s in SEEDS]
    return (max(steps) - min(steps)) / min(steps)


def test_chunk_steps_follow_the_data_set_not_the_seed():
    """At 1/20 of the cell's users, so a twentieth of its tiles and the
    draws' own noise some 4.5 times the cell's.  Range of the count over
    five seeds, read on four sets of seeds (PR 28, CPU): 0.25-0.41% with
    the ids pinned, 4.0-4.7% without; the limits are twice the widest of
    the one and about 0.6 of the least of the other."""
    data = _cut(0.05)
    pinned = _step_range(data)
    loose = _step_range(_without_id_seed(data))
    assert pinned < PINNED_RANGE
    assert loose > LOOSE_RANGE > 3 * PINNED_RANGE

