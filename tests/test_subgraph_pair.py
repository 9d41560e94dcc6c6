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
    # a second counter of the same shape finds the program again
    again = SG.SubgraphCounter(_cfg(seed=5), WorkerMesh(jax.devices()[:4]))
    assert again._fn is counter._fn


@pytest.mark.parametrize("algo", ["segment", "onehot"])
def test_row_tiled_neighbour_sum_equals_the_untiled(algo, toy_edges,
                                                    monkeypatch):
    """Tiles of 8 rows and 64 tail entries (37.5 row tiles, 20.9 entry
    tiles a worker: both last tiles overlap the one before) against one
    tile of everything: the same bits."""
    mesh = WorkerMesh(jax.devices()[:1])
    cfg = _cfg(overflow_algo=algo, overflow_row_tile=8,
               overflow_entry_tile=16)
    whole = SG.SubgraphCounter(cfg, mesh)
    whole.set_graph(toy_edges, 300)
    assert SG._gather_tiles(8, 40) == (65536, 524288)
    want = _blocks(whole, 2)
    monkeypatch.setattr(SG, "_gather_tiles", lambda max_degree, width: (8, 64))
    SG._FN_CACHE.clear()  # a program is traced with the tiles of its day
    tiled = SG.SubgraphCounter(cfg, mesh)
    assert tiled._fn is not whole._fn
    tiled.set_graph(toy_edges, 300)
    text = tiled._fn.lower(*tiled.installed(),
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

def test_install_and_run_leave_their_spans_and_records(toy_edges):
    SG._FN_CACHE.clear()  # the ledger prices a program when it is traced
    with telemetry.scope(True):
        counter = SG.SubgraphCounter(_cfg(), WorkerMesh(jax.devices()[:4]))
        counter.set_graph(toy_edges, 300)
        for _ in range(3):
            counter.count_colorings()
        spans = telemetry.tracer.records
        by_name = {}
        for r in spans:
            by_name.setdefault(r["span"], []).append(r)
        install = by_name["subgraph.install"][0]
        assert (install["vertices"], install["entries"],
                install["overflow_entries"]) == (300, 3000, 1336)
        tail = counter.installed()[2].size
        assert install["bytes"] == 8 * 300 * 8 + 12 * tail
        for child in ("subgraph.pad_csr", "subgraph.overflow",
                      "mesh.shard_array"):
            assert all(r["path"].startswith("subgraph.install/")
                       for r in by_name[child])
        assert len(by_name["mesh.shard_array"]) == 5
        assert [r["trials"] for r in by_name["subgraph.colorings"]] == [4] * 3
        rec = skew.ledger.summary()["subgraph.partition"]
        assert rec["padding_frac"] == pytest.approx(
            1 - 3000 / (300 * 8 + tail))
        # two distinct child shapes, two allgathers of a worker's 75
        # rows: the leaf's packed colours (one word for the 4
        # colourings), the star's 10 columns x 4 colourings; and the
        # allreduce of 4 counts; a block executes each once
        led = telemetry.ledger.summary()["subgraph.colorings"]
        assert led["executions"] == 3
        assert led["bytes_per_execution"] == 4 * (75 * (1 + 10 * 4) + 4)
    SG._FN_CACHE.clear()


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
    from perf import spec
    import os

    data = spec.load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perf", "configs", "subgraph-orkut-u5.json"))["data"]
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
