"""The install / run pair of ``harp_tpu.models.subgraph`` on the CPU:
``SubgraphCounter`` against ``count_template`` (its thin caller) on 1, 4
and 8 simulated workers, the row-tiled neighbour sum against the untiled
one, the block-colour query against the colours a block used, and the
program against the plain reference and both against brute force."""

import jax
import numpy as np
import pytest

from harp_tpu.models import subgraph as SG
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.utils import flightrec, skew, telemetry
from perf import graph_like
from perf.reference import subgraph as reference
from test_subgraph import brute_force_rooted_colorful

TOY = {"n_vertices": 300, "n_edges": 1500, "degree_max": 60,
       "degree_min": 1, "degree_law": "lognormal", "degree_sigma": 1.0,
       "id_seed": 13}


@pytest.fixture(scope="module")
def toy_edges():
    return graph_like.edges(TOY, 7)


def _cfg(**kw):
    return SG.SubgraphConfig(**{"n_trials": 6, "trial_chunk": 4,
                                "max_degree": 8, "seed": 2147489005, **kw})


def _configuration_data():
    """The ``data`` block of the cell's configuration file."""
    import os

    from perf import spec

    return spec.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perf", "configs", "subgraph-orkut-u5.json"))["data"]


def _spans_by_name():
    by_name = {}
    for r in telemetry.tracer.records:
        by_name.setdefault(r["span"], []).append(r)
    return by_name


def _blocks(counter, n):
    return np.concatenate([counter.count_colorings() for _ in range(n)])


@pytest.mark.parametrize("workers", [1, 4, 8])
def test_pair_returns_count_templates_counts(workers, toy_edges):
    """Bit for bit, hubs past ``max_degree`` included, on any mesh: the
    colourings are the program's own draw and do not move with it."""
    mesh = WorkerMesh(jax.devices()[:workers])
    cfg = _cfg()
    est, trials, overflow = SG.count_template(toy_edges, 300, cfg, mesh)
    counter = SG.SubgraphCounter(cfg, mesh)
    assert counter.set_graph(toy_edges, 300) == overflow == 1336
    rooted = _blocks(counter, 2)[:6]
    assert counter.estimates(rooted) == trials
    assert est == float(np.mean(trials))
    assert (counter.blocks_run, counter.colorings_run) == (2, 8)
    # the same counts as one worker's, whatever the mesh
    one = SG.SubgraphCounter(cfg, WorkerMesh(jax.devices()[:1]))
    one.set_graph(toy_edges, 300)
    assert (_blocks(one, 2)[:6] == rooted).all()


def test_run_is_one_dispatch_and_one_readback_and_uploads_nothing(toy_edges):
    counter = SG.SubgraphCounter(_cfg(), WorkerMesh(jax.devices()[:4]))
    counter.set_graph(toy_edges, 300)
    counter.count_colorings()  # compiles
    seen = {"dispatch": 0, "readback": 0, "h2d": 0, "compile": 0}

    def count(kind):
        return lambda *a: seen.__setitem__(kind, seen[kind] + 1)

    with flightrec.observe_dispatches(count("dispatch")), \
            flightrec.observe_readbacks(count("readback")), \
            flightrec.observe_h2d(count("h2d")), \
            flightrec.observe_compiles(count("compile")):
        for _ in range(3):
            counter.count_colorings()
    assert seen == {"dispatch": 3, "readback": 3, "h2d": 0, "compile": 0}
    # a second counter of the same shape and plan finds the program again
    again = SG.SubgraphCounter(_cfg(seed=5), WorkerMesh(jax.devices()[:4]))
    again.set_graph(toy_edges, 300)
    assert again._fn is counter._fn


@pytest.mark.parametrize("algo", ["segment", "onehot"])
def test_row_tiled_neighbour_sum_equals_the_untiled(algo, toy_edges,
                                                    monkeypatch):
    """Tiles of 8 rows and 64 tail entries against one tile of
    everything: the same bits.  At ``max_degree`` 16 the tiled counter's
    plan has two segments, of 186 and 114 rows (23.25 and 14.25 row tiles:
    both last tiles overlap the one before, as the last of the tail's 11.03
    entry tiles does), where the untiled one's is one segment of the whole width:
    the program without a plan."""
    mesh = WorkerMesh(jax.devices()[:1])
    cfg = _cfg(overflow_algo=algo, overflow_row_tile=8,
               overflow_entry_tile=16, max_degree=16)
    whole = SG.SubgraphCounter(cfg, mesh)
    whole.set_graph(toy_edges, 300)
    assert SG._gather_tiles(8, 40) == (32768, 524288)
    assert whole.plan == ((0, 300, 16),) and whole._order == ()
    want = _blocks(whole, 2)
    monkeypatch.setattr(SG, "_gather_tiles", lambda slots, width: (8, 64))
    SG._FN_CACHE.clear()  # a program is traced with the tiles of its day
    tiled = SG.SubgraphCounter(cfg, mesh)
    tiled.set_graph(toy_edges, 300)
    assert tiled._fn is not whole._fn
    assert tiled.plan == ((0, 186, 8), (186, 300, 16))
    text = tiled._fn.lower(*tiled._args(),
                           (tiled._key, np.int32(0))).as_text()
    assert "while" in text
    assert (_blocks(tiled, 2) == want).all()
    SG._FN_CACHE.clear()


def test_gather_tiles_follow_the_shapes():
    """At the cell's shapes: 4,096 rows x 128 slots x 80 columns (padded
    to 128 lanes) is the 256 MiB a tile may gather."""
    assert SG._gather_tiles(128, 80) == (4096, 524288)
    assert SG._gather_tiles(128, 40) == (4096, 524288)
    assert SG._gather_tiles(64, 280) == (2048, 131072)
    # a segment's tile is as many rows as its width leaves room for, up
    # to 32,768
    assert SG._gather_tiles(120, 80) == (4096, 524288)
    assert SG._gather_tiles(16, 80) == (32768, 524288)
    assert SG._gather_tiles(8, 80) == (32768, 524288)


# ---- the degree order and its plan ------------------------------------------

def _hub_on_one_worker():
    """64 vertices: vertex 1 joined to all the others (worker 0 of four),
    the rest a ring: every other worker's rows hold three."""
    ring = [(i, i + 1) for i in range(2, 63)] + [(63, 0), (0, 2)]
    return np.asarray([(1, i) for i in range(64) if i != 1] + ring,
                      np.int32), 64


def _all_rows_full():
    """The complete graph on 16 vertices: 15 neighbours each, over
    ``max_degree`` 8 on every row."""
    return np.asarray([(i, j) for i in range(16) for j in range(i)],
                      np.int32), 16


def _tail_rows_longhand(o_nbr, o_row, o_msk, cap):
    """One worker's tail rows, a vertex at a time: ``[(entries, owner,
    [neighbours])]`` in the order they are staged (fewest entries first,
    equal counts by owner, a vertex's rows in its tail's order)."""
    rows = []
    for v in np.unique(o_row[o_msk > 0]):
        mine = o_nbr[(o_row == v) & (o_msk > 0)].tolist()
        rows += [(len(mine[i:i + cap]), int(v), mine[i:i + cap])
                 for i in range(0, len(mine), cap)]
    return sorted(rows, key=lambda r: r[:2])


@pytest.mark.parametrize("graph", ["toy", "hub", "hub-wide", "full"])
@pytest.mark.parametrize("algo", ["segment", "onehot"])
@pytest.mark.parametrize("workers", [1, 4, 8])
def test_degree_ordered_sum_equals_the_whole_width(workers, algo, graph,
                                                   toy_edges, monkeypatch):
    """The installed program (rows in degree order, each segment at its
    own width, and under ``"segment"`` the tail as rows of at most
    ``max_degree`` slots, in the order of THEIR entries, each row's sum
    added to its owner) against the program without a plan (every row at
    ``max_degree``, in vertex order, the flat sorted tail scatter-added
    entry by entry) on the same installed graph and colours: counts of
    this size are whole numbers in float32, the same bits.  The plans
    are the widest over the workers: the hub's worker widens every
    worker's last segment.  ``hub-wide`` is the hub graph at a
    ``max_degree`` that holds the hub: a plan and no tail."""
    edges, n = {"toy": (toy_edges, 300), "hub": _hub_on_one_worker(),
                "hub-wide": _hub_on_one_worker(),
                "full": _all_rows_full()}[graph]
    monkeypatch.setattr(SG, "_gather_tiles", lambda slots, width: (8, 64))
    SG._FN_CACHE.clear()
    mesh = WorkerMesh(jax.devices()[:workers])
    cfg = _cfg(overflow_algo=algo, overflow_row_tile=8,
               overflow_entry_tile=16,
               max_degree={"full": 8, "hub-wide": 64}.get(graph, 24))
    counter = SG.SubgraphCounter(cfg, mesh)
    counter.set_graph(edges, n)
    loc, plan = counter.n_pad // workers, counter.plan
    assert plan[0][0] == 0 and plan[-1][1] == loc
    assert all(a[1] == b[0] and a[2] < b[2] for a, b in zip(plan, plan[1:]))
    msk = np.asarray(counter.installed()[1])
    counts = (msk > 0).sum(1).reshape(workers, loc)
    # every row in one tile (the hub graph's 8 a worker on 8) or every
    # row full: one segment of the whole width, the program without a plan
    whole_width = graph == "full" or (workers == 8 and "hub" in graph)
    if whole_width:
        assert plan == ((0, loc, cfg.max_degree),) and counter._order == ()
    else:
        (order,) = counter._order
        order = np.asarray(order).reshape(workers, loc)
        assert (np.sort(order, 1) == np.arange(loc)).all()  # a permutation
        ranked = np.take_along_axis(counts, order, 1)
        assert (np.diff(ranked, axis=1) >= 0).all()
        for start, stop, width in plan:
            assert width % 8 == 0 and width <= cfg.max_degree
            assert ranked[:, start:stop].max() <= width
    if graph == "hub" and workers == 4:
        assert plan == ((0, 15, 8), (15, 16, 24)) \
            and counts.max(1).tolist() == [24, 3, 3, 3]
    flat = tuple(np.asarray(a).reshape(workers, -1)
                 for a in counter.installed()[2:])
    if algo == "onehot" or whole_width:
        # the program's own tail is what installed() hands out
        assert counter.tail_plan is None
        assert all(a is b for a, b in zip(counter.installed()[2:],
                                          counter._tail))
    else:
        t_nbr, t_own, t_msk = (np.asarray(a).reshape(
            (workers, -1) + a.shape[1:]) for a in counter._tail)
        tail_plan, cap = counter.tail_plan, cfg.max_degree
        staged = t_msk.sum(2).astype(int)
        assert t_nbr.shape == t_msk.shape == staged.shape + (cap,)
        assert (t_msk[:, :, :-1] >= t_msk[:, :, 1:]).all()
        assert (np.diff(staged, axis=1) >= 0).all()  # fewest first
        most = 0
        for w in range(workers):
            want = _tail_rows_longhand(*(a[w] for a in flat), cap)
            most = max(most, len(want))
            front = staged.shape[1] - len(want)  # empty rows in front
            assert not staged[w, :front].any() and front >= 0
            assert [(c, o, nb[:c].tolist()) for c, o, nb in zip(
                staged[w, front:], t_own[w, front:], t_nbr[w, front:])
            ] == want
        assert staged.shape[1] == max(most, 1)
        if graph == "hub-wide":
            assert tail_plan == () and counter.overflow_entries == 0
        else:
            assert tail_plan[0][0] == 0 \
                and tail_plan[-1][1] == staged.shape[1]
            assert all(a[1] == b[0] and a[2] < b[2]
                       for a, b in zip(tail_plan, tail_plan[1:]))
            for start, stop, width in tail_plan:
                assert width % 8 == 0 and width <= cap
                assert staged[:, start:stop].max() <= width
        if graph == "hub" and workers == 4:  # 39 past 24, one worker
            assert tail_plan == ((0, 2, 24),) \
                and staged.tolist() == [[15, 24], [0, 0], [0, 0], [0, 0]]
    whole = SG.make_colorful_count_fn(
        counter.tpl, counter.k, mesh, algo, cfg.overflow_row_tile,
        draw_trials=counter.chunk)
    for block in range(2):
        want = np.asarray(whole(*counter.installed(),
                                (counter._key, np.int32(block))))
        assert (want > 0).any()
        assert (counter.count_colorings() == want).all()
    SG._FN_CACHE.clear()


@pytest.mark.parametrize("workers", [1, 4])
def test_degree_plan_at_the_configurations_size(workers):
    """The plan ``set_graph`` makes of the configuration's own degree
    sequence: every position in one segment, every row no wider than its
    segment, and under half of today's ``n x 128`` padded slots gathered
    (188,526,608 of 393,272,448 on one worker)."""
    data = _configuration_data()
    counts = np.minimum(graph_like.degree_sequence(data), 128)
    counts = np.sort(np.pad(counts, (0, -len(counts) % workers))
                     .reshape(workers, -1), axis=1)
    plan = SG.degree_plan(counts, 128)
    assert plan[0][0] == 0 and plan[-1][1] == counts.shape[1]
    assert all(a[1] == b[0] for a, b in zip(plan, plan[1:]))
    assert [w for _, _, w in plan] == list(range(8, 129, 8))
    for start, stop, width in plan:
        assert counts[:, start:stop].max() <= width
    slots = workers * SG.plan_slots(plan)
    assert slots < 0.52 * 393_272_448
    assert slots == {1: 188_526_608, 4: 188_653_376}[workers]
    # what the skew record then states: 3.7% of the executed slots are
    # padding (3.8% over four workers), where 47.7% of the staged were
    assert 1 - 234_370_166 / (slots + 54_903_737) < 0.04


@pytest.mark.parametrize("workers", [1, 4])
def test_tail_plan_at_the_configurations_size(workers):
    """The tail's plan as ``_tail_rows`` makes it, from the configuration's
    degree sequence alone: the 54,903,737 entries past 128 are 249,431
    full rows and 469,342 partial ones, 718,773 in all; on one worker 16
    segments of widths 8...128 whose 56,582,336 slots are the partial rows
    rounded up to 8 and nothing more (3.0% padding where the flat tail
    had none: the layout's price), over four workers 11 segments (the
    widest over the workers) of 58,678,688."""
    data = _configuration_data()
    past = np.maximum(graph_like.degree_sequence(data) - 128, 0)
    assert past.sum() == 54_903_737
    past = np.pad(past, (0, -len(past) % workers)).reshape(workers, -1)
    rows = [np.sort(np.concatenate(
        [np.full(int((p // 128).sum()), 128), p[p % 128 > 0] % 128]))
        for p in past]
    assert sum(int((r == 128).sum()) for r in rows) == 249_431
    assert sum(int((r < 128).sum()) for r in rows) == 469_342
    most = max(len(r) for r in rows)
    counts = np.stack([np.pad(r, (most - len(r), 0)) for r in rows])
    plan = SG.degree_plan(counts, 128)
    assert plan[0][0] == 0 and plan[-1][1] == most
    assert all(a[1] == b[0] for a, b in zip(plan, plan[1:]))
    for start, stop, width in plan:
        assert counts[:, start:stop].max() <= width
    slots = workers * SG.plan_slots(plan)
    assert (most, len(plan), slots) == {
        1: (718_773, 16, 56_582_336), 4: (179_988, 11, 58_678_688)}[workers]
    if workers == 1:
        assert [w for _, _, w in plan] == list(range(8, 129, 8))
        assert [stop - start for start, stop, _ in plan][::5] == [
            55_767, 33_509, 21_186, 261_775]
        assert slots == sum(int((-(-r // 8) * 8).sum()) for r in rows)
        # every tile on the fast gather rate, none over 32,768 rows
        tiles = [SG._segment_tile(stop - start, w, SG._gather_tiles(w, 128)[0])
                 for start, stop, w in plan]
        assert tiles[:2] + tiles[-1:] == [27_888, 24_968, 4_091]
        assert all(t * w % 128 == 0 and t * w // 128 % 8
                   for t, (_, _, w) in zip(tiles, plan))
    # what the skew record then states of a neighbour sum's slots, padded
    # part and tail: 4.4% padding (5.2% over four workers), 3.7% before
    padded = {1: 188_526_608, 4: 188_653_376}[workers]
    assert 1 - 234_370_166 / (padded + slots) < {1: 0.0439, 4: 0.0525}[workers]


def test_tail_rows_of_an_empty_tail_are_one_empty_row_and_no_plan():
    none = (np.zeros(4, np.int32), np.zeros(4, np.int32),
            np.zeros(4, np.float32))  # four workers' padding, no entry
    (t_nbr, t_own, t_msk), plan, rows = SG._tail_rows(none, 4, 8)
    assert (plan, rows) == ((), 0)
    assert t_nbr.shape == t_msk.shape == (4, 8) and t_own.shape == (4,)
    assert not t_msk.any()
    # one worker's single vertex with 17 entries past 8: rows of 8, 8, 1,
    # the fewest first, and the other worker's two empty rows in front
    one = (np.asarray([0, 0] + [0] * 17 + list(range(1, 18)), np.int32),
           np.asarray([0] * 19 + [5] * 17, np.int32),
           np.asarray([0] * 19 + [1] * 17, np.float32))
    (t_nbr, t_own, t_msk), plan, rows = SG._tail_rows(one, 2, 8)
    assert (plan, rows) == (((0, 3, 8),), 3)
    assert t_msk.sum(1).tolist() == [0, 0, 0, 1, 8, 8]
    assert t_own.tolist() == [0, 0, 0, 5, 5, 5]
    assert t_nbr[3:].tolist() == [[17] + [0] * 7, list(range(1, 9)),
                                  list(range(9, 17))]


def test_a_graph_without_a_tail_gets_a_program_without_a_tail_loop(
        monkeypatch):
    """The hub graph at a ``max_degree`` that holds the hub: rows of 3 and
    one of 63, so a plan, and an empty tail plan: no instruction of the
    compiled program stands under ``subgraph.tail``'s inner names, where
    the same graph cut at 24 has them inside loops."""
    monkeypatch.setattr(SG, "_gather_tiles", lambda slots, width: (8, 64))
    mesh = WorkerMesh(jax.devices()[:1])
    edges, n = _hub_on_one_worker()

    def compiled(max_degree):
        SG._FN_CACHE.clear()
        counter = SG.SubgraphCounter(_cfg(max_degree=max_degree), mesh)
        counter.set_graph(edges, n)
        return counter, counter._fn.lower(
            *counter._args(), (counter._key, np.int32(0))).compile().as_text()

    counter, text = compiled(64)
    assert counter.plan == ((0, 63, 8), (63, 64, 64))
    assert counter.tail_plan == () and "subgraph.tail." not in text
    counter, text = compiled(24)
    assert counter.tail_plan == ((0, 2, 24),)
    assert "subgraph.tail/subgraph.tail.rows" in text \
        and "subgraph.tail/subgraph.tail.add" in text
    # shorter than a tile: one gather and one add, no loop
    assert "subgraph.tail/while" not in text
    with pytest.raises(ValueError, match="tail's plan"):
        SG.make_colorful_count_fn(counter.tpl, counter.k, mesh,
                                  plan=counter.plan)
    SG._FN_CACHE.clear()


def test_degree_plan_merges_short_segments_and_keeps_full_rows_whole():
    # two rows of 3 are no tile of 32,768 x 8 slots: they ride with the 16s
    few = np.asarray([[3, 3] + [12] * 70_000 + [40] * 10])
    assert SG.degree_plan(few, 64) == ((0, 70_002, 16), (70_002, 70_012, 40))
    assert SG.plan_slots(SG.degree_plan(few, 64)) == 70_002 * 16 + 400
    assert SG.degree_plan(np.full((4, 100), 64), 64) == ((0, 100, 64),)
    # never over max_degree, which need be no multiple of 8; a row of
    # nothing still takes a slot
    assert SG.degree_plan(np.asarray([[0, 4, 4]]), 4) == ((0, 3, 4),)
    # the widest over the workers, position by position
    assert SG.degree_plan(np.asarray([[1, 2, 3], [1, 2, 30]]), 64) \
        == ((0, 3, 32),)


@pytest.mark.parametrize("rows, slots, most, tile", [
    (519_348, 128, 4096, 4090),   # 127 tiles; 4,090 groups of 128 ids
    (156_326, 64, 8192, 7818),    # 20 tiles of 3,909 groups, not 3,912
    (261_827, 40, 8192, 7952),    # 33 tiles: 32 would be 8,184 rows, ragged
    (72_000, 104, 4096, 4000),    # 18 even tiles as they are
    (300, 8, 8, 8),               # no tile of 8 rows is whole groups:
    (66, 16, 8, 8),               # the even ones
    (10, 4, 8, 5)])
def test_segment_tiles_are_even_and_no_multiple_of_8_groups(rows, slots,
                                                            most, tile):
    assert SG._segment_tile(rows, slots, most) == tile
    assert tile <= most and -(-rows // tile) * tile < rows + 0.01 * rows + 8
    if most > 128:
        assert tile * slots % 128 == 0 and tile * slots // 128 % 8


def test_installed_returns_the_five_arrays_as_they_were_staged(toy_edges,
                                                               monkeypatch):
    """The order is the counter's own: ``installed()`` is ``(nbr, msk,
    o_nbr, o_row, o_msk)`` in vertex order, row ``v`` of ``msk`` holding
    ``min(degree[v], max_degree)`` ones, the tail the rest."""
    monkeypatch.setattr(SG, "_gather_tiles", lambda slots, width: (8, 64))
    deg = graph_like.degree_sequence(TOY)
    counter = SG.SubgraphCounter(_cfg(max_degree=16),
                                 WorkerMesh(jax.devices()[:4]))
    counter.set_graph(toy_edges, 300)
    nbr, msk, o_nbr, o_row, o_msk = counter.installed()
    assert nbr.shape == msk.shape == (300, 16)
    assert o_nbr.shape == o_row.shape == o_msk.shape
    msk = np.asarray(msk)
    assert ((msk > 0).sum(1) == np.minimum(deg, 16)).all()
    assert (msk[:, :-1] >= msk[:, 1:]).all()  # a row's entries come first
    assert np.asarray(o_msk).sum() == np.maximum(deg - 16, 0).sum()
    # the counter's program reads its own tail rows; what is handed out
    # is the flat tail as the program without a plan takes it (each
    # worker's local rows ascending, padding in front), placed when asked,
    # and the benchmark's check (a) adds up on it: every vertex's entries
    # past max_degree
    assert counter.tail_plan and o_nbr is not counter._tail[0]
    local = np.asarray(o_row).reshape(4, -1)
    assert (np.diff(local, axis=1) >= 0).all() and local.max() < 75
    rows = (local + 75 * np.arange(4)[:, None]).reshape(-1)
    in_tail = np.zeros(300, np.int64)
    np.add.at(in_tail, rows, np.asarray(o_msk) > 0)
    assert (in_tail == np.maximum(deg - 16, 0)).all()
    again = counter.installed()
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(again, (nbr, msk, o_nbr, o_row, o_msk)))


def test_block_colour_query_returns_the_colours_the_block_used(toy_edges):
    """``block_colors(b)`` before the block, the explicit-colours program
    on those colours after it: the block's own counts."""
    mesh = WorkerMesh(jax.devices()[:4])
    counter = SG.SubgraphCounter(_cfg(), mesh)
    counter.set_graph(toy_edges, 300)
    for b in range(2):
        colours = np.asarray(counter.block_colors())
        assert colours.shape == (4, 300) and colours.dtype == np.int32
        assert set(np.unique(colours)) == set(range(5))
        assert (np.asarray(counter.block_colors(b)) == colours).all()
        explicit = SG.make_colorful_count_fn(counter.tpl, 5, mesh)
        pad = np.zeros((4, counter.n_pad), np.int32)
        pad[:, :300] = colours
        want = np.asarray(explicit(*counter.installed(),
                                   mesh.shard_array(pad, 1)))
        assert (counter.count_colorings() == want).all()
    # another block, another seed: other colours
    assert (np.asarray(counter.block_colors(0))
            != np.asarray(counter.block_colors(1))).any()
    other = SG.SubgraphCounter(_cfg(seed=6), mesh)
    other.set_graph(toy_edges, 300)
    assert (np.asarray(other.block_colors(0))
            != np.asarray(counter.block_colors(0))).any()


def test_run_before_install_is_refused():
    counter = SG.SubgraphCounter(_cfg(), WorkerMesh(jax.devices()[:1]))
    with pytest.raises(RuntimeError, match="set_graph"):
        counter.count_colorings()
    with pytest.raises(RuntimeError, match="set_graph"):
        counter.block_colors()
    with pytest.raises(ValueError, match="n_colors"):
        SG.SubgraphCounter(_cfg(n_colors=3))


# ---- program, reference, brute force ---------------------------------------

HUB_N = 12
HUB_EDGES = ([(0, i) for i in range(1, 10)]           # a hub of 9
             + [(1, 2), (2, 3), (3, 4), (4, 5), (5, 1), (6, 7), (8, 9),
                (9, 10), (10, 11), (11, 6), (2, 7), (2, 7)])  # a multi-edge


@pytest.mark.parametrize("tname", ["u3-path", "u5-tree", "u5-star"])
def test_program_reference_and_brute_force_agree(tname):
    """``k = s``, the hub's entries past ``max_degree`` 4 in the tail, a
    multi-edge counted twice by all three."""
    tpl = SG.TEMPLATES[tname]
    k = len(tpl)
    edges = np.asarray(HUB_EDGES, np.int32)
    mesh = WorkerMesh(jax.devices()[:4])
    counter = SG.SubgraphCounter(SG.SubgraphConfig(
        template=tname, n_trials=3, trial_chunk=3, max_degree=4, seed=11),
        mesh)
    assert counter.set_graph(edges, HUB_N) == 5 + 1  # the hub's, and 2's
    colours = np.asarray(counter.block_colors())
    got = counter.count_colorings()
    src, dst = reference.stage_edges(*graph_like.directed(edges), HUB_N,
                                     block=8)
    # brute force counts maps over SETS of edges: the doubled edge there
    # is counted by hand, as two parallel edges
    for t in range(3):
        want = reference.rooted_colourful_count(tpl, k, colours[t], src, dst,
                                                HUB_N, block=8)
        assert got[t] == want
    # the colourings side by side, as the cell's check asks: each one's own
    together = reference.rooted_colourful_count(tpl, k, colours.T, src, dst,
                                                HUB_N, block=8)
    assert together.dtype == np.float64 and (together == got).all()
    simple = sorted(set(map(tuple, HUB_EDGES)))
    plain = SG.SubgraphCounter(SG.SubgraphConfig(
        template=tname, n_trials=3, trial_chunk=3, max_degree=4, seed=11),
        mesh)
    plain.set_graph(np.asarray(simple, np.int32), HUB_N)
    colours = np.asarray(plain.block_colors())
    got = plain.count_colorings()
    for t in range(3):
        assert got[t] == brute_force_rooted_colorful(simple, HUB_N, tpl,
                                                     colours[t])
    assert reference.automorphisms(tpl) == SG._count_automorphism_roots(tpl)
    assert reference.estimate(120.0, tpl, k) == pytest.approx(
        plain.estimates([120.0])[0])


def test_reference_holds_all_colour_sets_and_imports_no_program():
    import inspect

    text = inspect.getsource(reference)
    assert "harp_tpu" not in text.replace("from ``harp_tpu``", "")
    assert reference.alone(np.asarray([0, 2, 4], np.int32), 5).shape == (3, 32)
    assert reference.alone(np.zeros((3, 4), np.int32), 5).shape == (3, 4, 32)


# ---- spans, the skew record, the ledger ------------------------------------

def test_install_and_run_leave_their_spans_and_records(toy_edges,
                                                       monkeypatch):
    """At ``max_degree`` 16 and tiles of 8 rows a worker's 75 rows are
    two segments, 41 rows at 8 slots and 34 at 16: 3,488 padded slots
    over the four workers where 4,800 are staged; and the 706 entries
    past 16 are 71 tail rows, at most 22 on a worker, staged as 4 x 22
    rows of 16 slots and summed as one segment of 16: 1,408 slots."""
    monkeypatch.setattr(SG, "_gather_tiles", lambda slots, width: (8, 64))
    SG._FN_CACHE.clear()  # the ledger prices a program when it is traced
    with telemetry.scope(True):
        counter = SG.SubgraphCounter(_cfg(max_degree=16),
                                     WorkerMesh(jax.devices()[:4]))
        counter.set_graph(toy_edges, 300)
        for _ in range(3):
            counter.count_colorings()
        by_name = _spans_by_name()
        install = by_name["subgraph.install"][0]
        assert (install["vertices"], install["entries"],
                install["overflow_entries"]) == (300, 3000, 706)
        assert counter.plan == ((0, 41, 8), (41, 75, 16))
        assert counter.tail_plan == ((0, 22, 16),)
        assert [a.shape for a in counter._tail] == [(88, 16), (88,), (88, 16)]
        assert (install["segments"], install["slots_staged"],
                install["slots_executed"]) == (2, 4800 + 1408, 3488 + 1408)
        assert (install["tail_segments"], install["tail_rows"],
                install["tail_slots_executed"]) == (1, 71, 1408)
        # nbr and msk, the tail's rows with their owners, and the order:
        # what is placed; the flat tail stays on the host
        assert install["bytes"] == 8 * 300 * 16 + (8 * 16 + 4) * 88 + 4 * 300
        for child in ("subgraph.pad_csr", "subgraph.overflow",
                      "subgraph.order", "subgraph.tail_rows",
                      "mesh.shard_array"):
            assert all(r["path"].startswith("subgraph.install/")
                       for r in by_name[child])
        assert len(by_name["mesh.shard_array"]) == 6
        assert [r["trials"] for r in by_name["subgraph.colorings"]] == [4] * 3
        # the skew record states the slots a neighbour sum executes, the
        # tail's as the rows gather them
        rec = skew.ledger.summary()["subgraph.partition"]
        assert rec["padding_frac"] == pytest.approx(
            1 - 3000 / (3488 + 1408))
        # two distinct child shapes, two allgathers of a worker's 75
        # rows: the leaf's packed colours (one word for the 4
        # colourings), the star's 10 columns x 4 colourings; and the
        # allreduce of 4 counts; a block executes each once
        led = telemetry.ledger.summary()["subgraph.colorings"]
        assert led["executions"] == 3
        assert led["bytes_per_execution"] == 4 * (75 * (1 + 10 * 4) + 4)
        # asked for, the flat tail is placed: three more placements,
        # outside the install
        tail = counter.installed()[2].size
        assert tail >= 706 and tail % 4 == 0
        assert len(_spans_by_name()["mesh.shard_array"]) == 9
    SG._FN_CACHE.clear()


def test_rows_all_full_install_as_before(toy_edges):
    """At ``max_degree`` 8 and the program's own tiles the toy graph's 300
    rows are one segment of the whole width: the program without a plan,
    which takes the flat tail: five placements, no order, no tail rows,
    and executed slots = staged slots."""
    with telemetry.scope(True):
        counter = SG.SubgraphCounter(_cfg(), WorkerMesh(jax.devices()[:4]))
        counter.set_graph(toy_edges, 300)
        by_name = _spans_by_name()
        install = by_name["subgraph.install"][0]
        tail = counter.installed()[2].size
        assert counter.plan == ((0, 75, 8),) and counter._order == ()
        assert counter.tail_plan is None
        assert counter.installed()[2] is counter._tail[0]
        assert install["slots_executed"] == install["slots_staged"] \
            == 300 * 8 + tail
        assert (install["segments"], install["tail_segments"],
                install["tail_rows"], install["tail_slots_executed"]) \
            == (1, 0, 0, tail)
        assert install["bytes"] == 8 * 300 * 8 + 12 * tail
        assert "subgraph.tail_rows" not in by_name
        assert len(_spans_by_name()["mesh.shard_array"]) == 5
        assert skew.ledger.summary()["subgraph.partition"][
            "padding_frac"] == pytest.approx(1 - 3000 / (300 * 8 + tail))


def test_spans_cost_one_flag_test_when_off(toy_edges):
    telemetry.tracer.reset()
    counter = SG.SubgraphCounter(_cfg(), WorkerMesh(jax.devices()[:1]))
    counter.set_graph(toy_edges, 300)
    counter.count_colorings()
    assert telemetry.tracer.records == []


# ---- the generator ---------------------------------------------------------

def test_degree_sequence_is_the_data_sets_and_edges_the_seeds():
    deg = graph_like.degree_sequence(TOY)
    assert (deg.sum(), deg.min(), deg.max()) == (3000, 1, 60)
    assert (graph_like.degree_sequence(dict(TOY)) == deg).all()
    hubs = np.argsort(deg)[-5:]
    for seed in (0, 7, 2147489005):
        e = graph_like.edges(TOY, seed)
        assert e.shape == (1500, 2) and e.dtype == np.int32
        assert (e[:, 0] != e[:, 1]).all()  # self-loops re-drawn
        # the same degrees on the same ids at every seed
        assert (np.bincount(e.ravel(), minlength=300) == deg).all()
        assert (np.argsort(np.bincount(e.ravel()))[-5:] == hubs).all() or \
            set(np.argsort(np.bincount(e.ravel()))[-5:]) == set(hubs)
    assert (graph_like.edges(TOY, 0) != graph_like.edges(TOY, 7)).any()
    assert (graph_like.edges(TOY, 7) == graph_like.edges(TOY, 7)).all()
    other_ids = graph_like.degree_sequence({**TOY, "id_seed": 14})
    assert (other_ids != deg).any() and other_ids.sum() == 3000
    src, dst = graph_like.directed(graph_like.edges(TOY, 7))
    assert len(src) == len(dst) == 3000
    assert (np.bincount(src, minlength=300) == deg).all()


def test_degree_sequence_at_the_configurations_size():
    """The configuration's counts, to the entry (com-Orkut's vertices,
    edges and ends), and the split at ``max_degree`` 128 that every seed
    then has."""
    data = _configuration_data()
    deg = graph_like.degree_sequence(data)
    assert len(deg) == 3_072_441 and deg.sum() == 234_370_166
    assert (deg.min(), deg.max()) == (1, 33_313)
    assert np.median(deg) == 46
    # 15.3% of the vertices have more than 128 neighbours, and 23.4% of
    # the entries ride the exact tail
    assert int((deg > 128).sum()) == 471_066
    assert int(np.maximum(deg - 128, 0).sum()) == 54_903_737
    assert not deg.flags.writeable  # made once, handed out as it is
    with pytest.raises(ValueError, match="degree law"):
        graph_like.degree_sequence({**data, "degree_law": "zipf"})
