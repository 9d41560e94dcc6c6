"""Device ops by the program's own names (PR 40): the ``jax.named_scope``s
of the five step programs reach their optimized HLO and change nothing
else in it, and ``telemetry.scopes`` keeps the map from instruction to
``op_name`` that ``perf/scope_reduce.py`` reads a trace with.  Toy shapes,
the cells' default arms, on the CPU: no number here is a speed."""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.models import kmeans, lda, mfsgd, mlp
from harp_tpu.models import subgraph as SG
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.utils import flightrec, telemetry
from perf import scope_reduce
from test_chip_compile import metadata_stripped

# every scope the default arm of each step program runs (PERF.md section
# 3 lists them with what reads each) and the least share of a toy
# program's instructions with an ``op_name`` that lie under one: what is
# left is the glue of ``shard_map`` and of the loops (parameters, tuples,
# counters), at a toy size a large part of few instructions
VOCABULARY = {
    "kmeans": ({"kmeans.cast", "kmeans.assign", "kmeans.sums",
                "kmeans.combine", "kmeans.update"}, 0.6),
    "mfsgd": ({"mfsgd.kernel", "mfsgd.slices", "mfsgd.rotate",
               "mfsgd.loss"}, 0.75),
    "lda": ({"lda.kernel", "lda.chain", "lda.touched", "lda.rotate",
             "lda.nk", "lda.slices", "lda.keys"}, 0.75),
    "mlp": ({"mlp.layer1", "mlp.layer2", "mlp.layer3", "mlp.loss",
             "mlp.accuracy", "mlp.combine", "mlp.update", "mlp.batch",
             "mlp.order", "mlp.steps"}, 0.75),
    "subgraph": ({"subgraph.draw", "subgraph.singleton",
                  "subgraph.sum.leaf", "subgraph.sum.t3",
                  "subgraph.allgather", "subgraph.padded",
                  "subgraph.order.take", "subgraph.order.put",
                  "subgraph.tail", "subgraph.tail.rows", "subgraph.tail.add",
                  "subgraph.convolve", "subgraph.count"},
                 0.9),
}


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """JAX leaves ``op_name`` out of the persistent cache's key, so with a
    cache on, a program compiled with its scopes and then without them is
    a hit that comes back WITH them.  This process has no cache when
    pytest runs alone (``conftest.py`` sets the variable after jax is
    imported), but an xdist worker inherits the variable and has one: a
    compile of a second or more is kept (the subgraph program's, on a
    loaded machine), and so is every compile once a test of the same
    worker has run a cell of the benchmark (``perf/harness.py`` sets both
    thresholds to nothing).  The compiles of this file read what the
    compiler made of THEIR source: no cache around them."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mesh():
    return WorkerMesh(jax.devices()[:2])


@pytest.fixture(scope="module")
def builders(mesh):
    """``{app: build}``; ``build()`` traces the app's step program anew
    (a fresh ``jax.jit``, so a patched ``jax.named_scope`` is seen),
    lowers it for its arguments and returns the optimized HLO text.  The
    host-side installs are made once."""

    def optimized(make, args):
        return lambda: make().lower(*args).compile().as_text()

    rng = np.random.default_rng(0)
    out = {}

    points = jax.device_put(
        rng.standard_normal((512, 16)).astype(np.float32),
        mesh.sharding(mesh.spec(0, ndim=2)))
    centroids = jax.device_put(np.asarray(points[:8]), mesh.replicated())
    out["kmeans"] = optimized(
        lambda: kmeans.make_fit_fn(mesh, kmeans.KMeansConfig(k=8, iters=3)),
        (points, centroids))

    mf_cfg = mfsgd.MFSGDConfig(rank=8, algo="pallas")
    mf = mfsgd.MFSGD(300, 200, mf_cfg, mesh)
    mf.set_ratings(rng.integers(0, 300, 8000), rng.integers(0, 200, 8000),
                   rng.standard_normal(8000).astype(np.float32))
    out["mfsgd"] = optimized(
        lambda: mfsgd.make_multi_epoch_fn(mesh, mf_cfg, 2),
        (mf.W, mf.H, *mf._blocks))

    lda_cfg = lda.LDAConfig(n_topics=16, d_tile=128, w_tile=128,
                            entry_cap=256)
    topics = lda.LDA(200, 600, lda_cfg, mesh)
    topics.set_tokens(rng.integers(0, 200, 6000), rng.integers(0, 600, 6000))
    out["lda"] = optimized(
        lambda: lda.make_multi_epoch_fn(mesh, lda_cfg, 600, 1,
                                        topics._count_bounds),
        topics._epoch_args())

    mlp_cfg = mlp.MLPConfig(sizes=(20, 16, 8, 4))
    trainer = mlp.MLPTrainer(mlp_cfg, mesh)
    trainer.load_resident(
        rng.standard_normal((512, 20)).astype(np.float32),
        rng.integers(0, 4, 512).astype(np.int32), batch_size=64)
    xs, ys, bpw, nb = trainer._resident
    out["mlp"] = optimized(
        lambda: mlp.make_epoch_fn(mesh, mlp_cfg, bpw, nb, 2)[0],
        (trainer.params, trainer.opt_state, xs, ys,
         trainer._resident_key(0)))

    # a graph whose rows differ in degree and with a hub past max_degree,
    # and tiles small enough that a toy has segments, loops and a tail
    edges = rng.integers(0, 400, (900, 2))
    edges = np.concatenate([edges[edges[:, 0] != edges[:, 1]], np.stack(
        [np.zeros(399, np.int64), np.arange(1, 400)], 1)])
    sg_cfg = SG.SubgraphConfig(template="u5-tree", max_degree=16,
                               trial_chunk=4, n_trials=4)
    small_tiles = mock.patch.object(SG, "_gather_tiles",
                                    lambda slots, width: (8, 64))
    with small_tiles:
        SG._FN_CACHE.clear()
        counter = SG.SubgraphCounter(sg_cfg, mesh)
        counter.set_graph(edges, 400)
        SG._FN_CACHE.clear()
    assert len(counter.plan) > 1 and counter.overflow_entries > 2 * 64

    def build_subgraph():
        with small_tiles:  # the tiles are read as the program is traced
            SG._FN_CACHE.clear()
            try:
                return SG.make_colorful_count_fn(
                    counter.tpl, counter.k, mesh, sg_cfg.overflow_algo,
                    sg_cfg.overflow_row_tile, draw_trials=counter.chunk,
                    plan=counter.plan, tail_plan=counter.tail_plan).lower(
                        *counter._args(),
                        (counter._key, np.int32(0))).compile().as_text()
            finally:
                SG._FN_CACHE.clear()

    out["subgraph"] = build_subgraph
    return out


# ---- (1) the scopes reach the optimized program ---------------------------

@pytest.mark.parametrize("app", sorted(VOCABULARY))
def test_default_arm_carries_its_vocabulary(app, builders):
    scopes = telemetry.ScopeMap()
    assert scopes.add_text(builders[app](), app)
    (module, instructions), = scopes.modules.items()
    assert module.startswith("jit_") and scopes.labels[module] == app
    paths = [scope_reduce.scope_path(o)[0] for o in instructions.values()]
    wanted, least = VOCABULARY[app]
    assert {s for p in paths for s in p} == wanted
    assert sum(1 for p in paths if p) / len(paths) >= least
    assert scopes.lookup(module, next(iter(instructions))) is not None
    assert scopes.lookup(module, "no_such_instruction") is None
    assert scopes.lookup("no_such_module", "fusion.1") is None


def test_backward_ops_keep_their_layers_scope(builders):
    scopes = telemetry.ScopeMap()
    scopes.add_text(builders["mlp"]())
    (instructions,) = scopes.modules.values()
    reduced = {scope_reduce.scope_path(o) for o in instructions.values()}
    assert (("mlp.steps", "mlp.layer1"), False) in reduced   # x . W1
    assert (("mlp.steps", "mlp.layer1"), True) in reduced    # W1's gradient
    assert any("transpose(jvp(mlp.layer1))" in o
               for o in instructions.values())


def test_an_op_inside_a_loop_keeps_the_scope_around_the_loop(builders):
    scopes = telemetry.ScopeMap()
    scopes.add_text(builders["subgraph"]())
    (instructions,) = scopes.modules.values()
    inside = [o for o in instructions.values()
              if "subgraph.tail/while/body" in o]
    assert inside
    for o in inside:
        path, _ = scope_reduce.scope_path(o)
        # the tail's own inner names stand under it, and under nothing of
        # the padded part's or the order's (their shares stay disjoint)
        assert path[0] in ("subgraph.sum.leaf", "subgraph.sum.t3") \
            and path[1] == "subgraph.tail" and set(path[2:]) <= {
                "subgraph.tail.rows", "subgraph.tail.add"}
    assert {scope_reduce.scope_path(o)[0][-1] for o in inside} >= {
        "subgraph.tail.rows", "subgraph.tail.add"}
    # the degree order's scatter sits in the loop over a segment's tiles
    assert any("while/body" in o and o.count("subgraph.order.put")
               for o in instructions.values())


@pytest.mark.parametrize("op_name,path,backward", [
    ("jit(f)/jvp(mlp.layer1)/dot_general", ("mlp.layer1",), False),
    ("jit(f)/transpose(jvp(mlp.layer1))/dot_general", ("mlp.layer1",), True),
    ("jit(program)/shard_map/subgraph.sum.t3/subgraph.tail/while/body/"
     "closed_call/subgraph.tail.add/scatter-add",
     ("subgraph.sum.t3", "subgraph.tail", "subgraph.tail.add"), False),
    ("jit(run)/vmap(checkpoint(kmeans.assign))/argmin",
     ("kmeans.assign",), False),
    # XLA's own names and a function's are not scopes
    ("jit(many)/shard_map/broadcast.25", (), False),
    ("jit(a.b)/pjit(c.d)/add", (), False),
    ("jit(f)/Mlp.Layer1/add", (), False),
    ("copy.3", (), False), ("", (), False), (None, (), False),
])
def test_scope_path(op_name, path, backward):
    assert scope_reduce.scope_path(op_name) == (path, backward)


@pytest.mark.parametrize("template,names", [
    ("u5-tree", {"()": "leaf", "(()())": "t3"}),
    ("u3-path", {"()": "leaf", "(())": "t2"}),
    ("u5-star", {"()": "leaf"}),
    ("u7-tree", {"()": "leaf", "(()())": "t3"}),
    # two shapes of one size get a letter each, in canonical order
    ([-1, 0, 1, 2, 0, 4, 4], {"()": "leaf", "(())": "t2", "((()))": "t3a",
                              "(()())": "t3b"}),
])
def test_neighbour_sums_have_one_stable_name_a_shape(template, names):
    tpl = SG.TEMPLATES.get(template, template) \
        if isinstance(template, str) else template
    assert SG._sum_scope_names(tpl) == names


# ---- (2) scopes change names only -----------------------------------------

@pytest.mark.parametrize("app", sorted(VOCABULARY))
def test_scopes_change_names_only(app, builders):
    named = builders[app]()
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        bare = builders[app]()
    assert "op_name=" in named and any(
        s in named for s in VOCABULARY[app][0])
    assert not any(s in bare for s in VOCABULARY[app][0])
    assert metadata_stripped(named) == metadata_stripped(bare)


# ---- (3) the map is made with telemetry on, once, and never off -----------

def _counting():
    events = []
    return events, flightrec.observe_compiles(
        lambda kind, seconds: events.append(kind))


def test_telemetry_off_lowers_and_compiles_nothing_extra():
    def f(x):
        with jax.named_scope("toy.double"):
            return x * 2.0

    x = jnp.arange(8.0)
    tracked = flightrec.track(jax.jit(f), "toy")
    with telemetry.scope(False):
        events, watching = _counting()
        with watching:
            tracked(x), tracked(x)
        assert events.count("compile") == 1  # the program's own, as ever
        assert telemetry.scopes.summary() == {"modules": {}, "skipped": {}}


def test_map_is_made_once_for_a_jit_and_for_a_compiled(tmp_path):
    def f(x):
        with jax.named_scope("toy.double"):
            return jnp.sin(x) * 2.0

    def g(x):
        with jax.named_scope("toy.halve"):
            return jnp.cos(x) / 2.0

    x = jnp.arange(8.0)
    with telemetry.scope(True):
        jitted = flightrec.track(jax.jit(f), "toy.jit")
        aot = flightrec.track(jax.jit(g).lower(x).compile(), "toy.aot")
        neither = flightrec.track(lambda x: x + 1, "toy.python")
        events, watching = _counting()
        with watching:
            for _ in range(3):
                jitted(x), aot(x), neither(x)
        # the map's lowering and the program's own: nothing on later calls
        assert events.count("compile") == 2
        made = telemetry.scopes.summary()
        assert {m["label"] for m in made["modules"].values()} == {
            "toy.jit", "toy.aot"}
        assert list(made["skipped"]) == ["toy.python"]
        by_label = {telemetry.scopes.labels[m]: set(
            s for o in i.values() for s in scope_reduce.scope_path(o)[0])
            for m, i in telemetry.scopes.modules.items()}
        assert by_label == {"toy.jit": {"toy.double"},
                            "toy.aot": {"toy.halve"}}
        assert flightrec.transfers.dispatches == 9
        out = tmp_path / "telemetry.jsonl"
        telemetry.export(str(out))
        rows = telemetry.load_rows(str(out))["scope"]
        assert {(r["module"], r["instruction"]): r["op_name"]
                for r in rows} == {
                    (m, i): o for m, ins in telemetry.scopes.modules.items()
                    for i, o in ins.items()}
        assert all(r["kind"] == "scope" and r["label"] for r in rows)
        # a reset forgets the map and which programs were read
        telemetry.scopes.reset()
        jitted(x)
        assert list(telemetry.scopes.labels.values()) == ["toy.jit"]


def test_names_are_in_the_cache_key_only_while_telemetry_is_on():
    option = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, option)
    with telemetry.scope(False), telemetry.current_names():
        assert getattr(jax.config, option) == before
    with telemetry.scope(True):
        with telemetry.current_names():
            assert getattr(jax.config, option) is True
        assert getattr(jax.config, option) == before


def test_a_cache_filled_by_other_source_gives_no_stale_names(tmp_path):
    """JAX leaves ``op_name`` out of the persistent cache's key: a program
    compiled without its scopes and then with them is a hit, and the text
    that comes back has none.  The map is read under a key that holds the
    names, and so is the dispatch that follows it."""
    from jax.experimental.compilation_cache import compilation_cache

    options = {"jax_compilation_cache_dir": str(tmp_path),
               "jax_persistent_cache_min_compile_time_secs": 0.0,
               "jax_persistent_cache_min_entry_size_bytes": -1,
               "jax_enable_compilation_cache": True}
    before = {name: getattr(jax.config, name) for name in options}

    def program(scope):
        def f(x):
            with scope("toy.square"):
                return jnp.tanh(x) ** 2
        return jax.jit(f)

    x = jnp.arange(16.0)
    try:
        for name, value in options.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        program(lambda name: contextlib.nullcontext()).lower(x).compile()
        stale = program(jax.named_scope).lower(x).compile().as_text()
        assert "toy.square" not in stale    # the trap
        with telemetry.scope(True):
            flightrec.track(program(jax.named_scope), "toy")(x)
            (instructions,) = telemetry.scopes.modules.values()
            assert any("toy.square" in o for o in instructions.values())
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
