"""chip_smoke.py's full-width programs, COMPILED for a v5e without one.

The sandbox's libtpu gives a compile-only TPU client
(``jax.experimental.topologies``): real XLA:TPU and Mosaic compiles —
VMEM limits, layout and tiling refusals — against ``TPU v5 lite``
devices that cannot execute.  This is the check that runs before chip
time is spent: a shape the compiler refuses (the int8 tile search on
250,000 rows/worker, the MF-SGD 128-multiple gate on eight half-slices)
fails here, on the CPU.  HL201 only LOWERS the registry's toy shapes.
Shapes only, no data; what the chip adds is execution.

The compiles run in ONE child process (this file, as a script): a
process that has created the TPU client gets empty ``/device:TPU``
planes in every later ``jax.profiler`` trace, which would starve
``op_breakdown``'s device filter in the tests that follow.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def table_sized_writes(hlo: str, sizes: set,
                       mosaic: str = "tpu_custom_call") -> list:
    """``[name, opcode]`` of every instruction of a compiled module's text
    whose result is ONE array of an element count in ``sizes`` and that
    writes it: parameters, tuple elements and bitcasts name a buffer that
    is there, and the Mosaic call is the one writer allowed (its tables
    are aliased).  Instructions inside fusions count with the rest."""
    import math
    import re

    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        name, dims, op = m.groups()
        n = math.prod(int(d) for d in dims.split(",") if d)
        if (n in sizes
                and op not in ("parameter", "get-tuple-element", "bitcast")
                and not (op == "custom-call" and mosaic in line)):
            found.append([name, op])
    return found


def metadata_stripped(hlo: str) -> str:
    """A compiled module's text without what names its instructions'
    origin: every ``metadata={...}`` (``op_name``, source line, stack
    frame) and the tables of files, functions, locations and stack frames
    they index.  What is left is the program."""
    import re

    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*\n?", "", hlo, flags=re.M)


def program_alone(hlo: str) -> str:
    """:func:`metadata_stripped`, and besides: every instruction and
    computation renamed by the order of its first appearance, and a Mosaic
    call's serialized body left out.  On the TPU compiler two things
    follow an instruction's ``op_name`` that are not metadata: the numbers
    XLA gives its instructions (the same program under other names comes
    out as ``reshape.1945`` where it was ``reshape.1897``), and the name of
    a Mosaic call, which is the part of its ``op_name`` before
    ``pallas_call`` (``closed_call.23`` becomes ``lda.kernel.4``); and the
    kernel's body travels as bytecode with its own source locations.  Two
    texts that are equal here are the same instructions in the same
    order."""
    import re

    names: dict = {}
    hlo = re.sub(r'(custom_call_target="tpu_custom_call".*?'
                 r'backend_config=)\{.*?\}(?=[,\s]|$)', r"\1{}",
                 metadata_stripped(hlo), flags=re.M)
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%n{len(names)}"),
                  hlo)


def _without_scopes():
    """``jax.named_scope`` as a context that names nothing."""
    import contextlib
    from unittest import mock

    import jax

    return mock.patch.object(jax, "named_scope",
                             lambda name: contextlib.nullcontext())


def gathers_and_scatters(hlo: str) -> int:
    """How many ``gather`` and ``scatter`` instructions a compiled
    module's text holds, those inside fusions with the rest."""
    import re

    return len(re.findall(r" = \S+ (?:gather|scatter)\(", hlo))


def _tile_loops(hlo: str):
    """``(while line, [[tile_rows, slots], ...])`` of every ``while`` loop
    of a compiled module's text that gathers table rows by a
    ``[tile_rows, slots]`` tile of neighbour ids inside its body
    (``f32[tile_rows, slots, 128]`` rows or ``u32[tile_rows, slots]``
    packed colours)."""
    import re

    comps = {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?^\}", hlo, re.M | re.S)}

    def reach(name, seen):
        if name in comps and name not in seen:
            seen.add(name)
            for callee in re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                    comps[name]):
                reach(callee, seen)
        return seen

    for line in hlo.splitlines():
        m = re.search(r"^.* while\(.*body=%([\w.\-]+)", line)
        if not m:
            continue
        body = "".join(comps[c] for c in reach(m.group(1), set()))
        tiles = re.findall(r" = (?:f32\[(\d+),(\d+),128\]|u32\[(\d+),(\d+)\])"
                           r"\S* gather\(", body)
        yield line, [[int(a or c), int(b or d)] for a, b, c, d in tiles]


def _under_the_tail(line: str) -> bool:
    return "subgraph.tail/while" in line


def padded_part_loops(hlo: str) -> list:
    """``[rows, tile_rows, slots]`` of every ``while`` loop of a compiled
    module's text that carries a segment of the degree order (a 1-D
    ``s32`` array) and gathers by it (:func:`_tile_loops`), the tail's
    loops, which carry their rows' owners, set aside by their scope."""
    import re

    return [[int(re.search(r"[ /]s32\[(\d+)\]", line).group(1)), *tile]
            for line, tiles in _tile_loops(hlo)
            if not _under_the_tail(line) for tile in tiles]


def tail_loops(hlo: str) -> list:
    """``[tile_rows, slots]`` of every ``while`` loop under the scope
    ``subgraph.tail`` that gathers (:func:`_tile_loops`): the tail rows'
    tiles (the text must carry its ``op_name``s)."""
    return [tile for line, tiles in _tile_loops(hlo)
            if _under_the_tail(line) for tile in tiles]


def _compile_all() -> dict:
    """Every check, in the child: {check name: {program: mosaic calls}}."""
    import functools
    import math
    import re
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    import chip_smoke
    from harp_tpu.models import kmeans, mfsgd
    from harp_tpu.ops.kernel_registry import KERNELS
    from harp_tpu.parallel.mesh import WorkerMesh

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    out = {"device_kind": devices[0].device_kind, "n_devices": len(devices)}

    def mosaic_calls(fn, sds):
        return fn.lower(*sds).compile().as_text().count(chip_smoke.MOSAIC_CALL)

    def scopes_in(hlo, app):
        """The ``<app>.<part>`` segments of the text's ``op_name``s."""
        return sorted({seg for op_name in re.findall(r'op_name="([^"]*)"', hlo)
                       for seg in re.findall(rf"\b{app}\.[a-z0-9.]+\b",
                                             op_name)})

    def mfsgd_args(algo, n_dev, ns, u_bound, ibc, ne, c):
        """W, H and a half-slice's block: ``ne`` entries ``c`` wide with
        their offsets, or for the kernel ``ne`` chunks with their
        metadata (cu / ci / cv / meta)."""
        i32, f32 = jnp.int32, jnp.float32
        rows = ns * n_dev
        blocks = [((rows, ne, c), i32), ((rows, ne, c), i32),
                  ((rows, ne, c), f32), ((rows, ne), i32)]
        if algo == "dense":
            blocks.append(((rows, ne), i32))
        return [sds(s, dt) for s, dt in [
            ((u_bound * n_dev, 64), f32), ((ibc * ns, 64), f32), *blocks]]

    for n_dev in (1, 4):
        mesh = WorkerMesh(devices[:n_dev])
        rows = mesh.sharding(mesh.spec(0, ndim=2))
        progs = {}

        def sds(shape, dtype, sharding=None):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=sharding or mesh.sharding(
                    mesh.spec(0, ndim=len(shape))))

        # KMeans graded config #1: the three arms phase_kmeans runs
        km = chip_smoke.KMEANS_FULL
        n, d, k = km["n"], km["d"], km["k"]
        for arm, kw in (
                ("xla_f32", {"use_pallas": False}),
                ("pallas_int8", {"quantize": "int8", "use_pallas": True}),
                ("xla_int8", {"quantize": "int8", "use_pallas": False})):
            cfg = kmeans.KMeansConfig(k=k, iters=km["iters"], **kw)
            assert kmeans.partials_arm(cfg, n // n_dev, d) == arm
            cents = sds((k, d), jnp.float32, mesh.replicated())
            pts = ((sds((n, d), jnp.int8, rows),
                    sds((d,), jnp.float32, mesh.replicated()))
                   if cfg.quantize else sds((n, d), jnp.float32, rows))
            progs[f"kmeans.{arm}"] = mosaic_calls(
                kmeans.make_fit_fn(mesh, cfg), (pts, cents))

        # MF-SGD at the MovieLens-20M shape (benchmark()'s defaults)
        for algo in ("pallas", "dense"):
            cfg = mfsgd.MFSGDConfig(rank=64, algo=algo)
            ut, it = mfsgd.tiles(cfg)
            ns = mfsgd.rotate_chunks_resolved(cfg) * n_dev
            _, _, u_bound, ibc = mfsgd._dense_bounds(
                138_493, 26_744, n_dev, ns, ut, it)
            # one entry per (u_tile × i_tile) sub-tile of a block: 20M
            # ratings over the grid average well under entry_cap per tile
            ne, c = (u_bound // ut) * (ibc // it), cfg.entry_cap
            if algo == "pallas":  # every entry all its 512-wide chunks
                ne, c = ne * (c // 512), 512
            progs[f"mfsgd.{algo}"] = mosaic_calls(
                mfsgd.make_multi_epoch_fn(mesh, cfg, epochs=3),
                mfsgd_args(algo, n_dev, ns, u_bound, ibc, ne, c))
        out[f"full_width_{n_dev}"] = progs

    # the benchmark's cell mfsgd-epochs (perf/configs/mfsgd-ml20m-x4-r64):
    # one chip, 553,972 users, blocks of 4 epochs, a chunk list longer
    # than any of its seeds staged (133,611–136,739 chunks a half-slice).
    # The kernel prefetches the list's metadata into SMEM whole.
    mesh = WorkerMesh(devices[:1])
    cfg = mfsgd.MFSGDConfig(rank=64, algo="pallas")
    _, _, u_bound, ibc = mfsgd._dense_bounds(
        553_972, 26_744, 1, 2, *mfsgd.tiles(cfg))
    out["mfsgd_cell"] = {
        "u_bound": u_bound,
        "mosaic_calls": mosaic_calls(
            mfsgd.make_multi_epoch_fn(mesh, cfg, epochs=4),
            mfsgd_args("pallas", 1, 2, u_bound, ibc, 140_000, 512))}

    # the benchmark's cell lda-sweeps (perf/configs/lda-enwiki-v1m-k1k):
    # one chip, 1k topics over a 1M-word vocabulary, 6,656 documents, one
    # sweep a program, the chunk list every seed stages (13 runs of 1,381
    # chunks of 128 slots a half-slice) and count bounds above its
    # corpus' (a 6,411-token document, a word of 204,574 tokens: 2 and 3
    # gather planes).  What the chip must hold: the arguments and the
    # program's temporaries, the state donated, both tables topic-major
    # as the device stores them and going through the kernel in place.
    # What the sweep must not do: write a buffer the size of the table or
    # of a rotation half-slice with anything but the kernel.
    from harp_tpu.models import lda

    cfg = lda.LDAConfig(n_topics=1000)

    def lda_sweep():
        return lda.make_multi_epoch_fn(
            mesh, cfg, 1_000_000, 1, (7000, 210_000)).lower(*[
                jax.ShapeDtypeStruct(shape, dt, sharding=mesh.sharding(spec))
                for (shape, dt), spec in zip(
                    lda.epoch_arg_shapes(1, 6656, 1_000_000, cfg,
                                         entries_per_row=13 * 1381),
                    lda._epoch_in_specs(mesh, cfg))]).compile()

    compiled = lda_sweep()
    mem, hlo = compiled.memory_analysis(), compiled.as_text()
    with _without_scopes():
        bare = lda_sweep().as_text()
    table = lda.epoch_arg_shapes(1, 6656, 1_000_000, cfg)[1]
    elems = int(np.prod(table[0]))
    out["lda_cell"] = {
        "mosaic_calls": hlo.count(chip_smoke.MOSAIC_CALL),
        "table_shape": list(table[0]),
        "table_bytes": elems * table[1].itemsize,
        "aliased_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "table_sized_writes": table_sized_writes(hlo, {elems, elems // 2}),
        "scopes": scopes_in(hlo, "lda"),
        "scopes_without": scopes_in(bare, "lda"),
        "scopes_change_names_only": program_alone(hlo) == program_alone(bare),
        "held_gb": round((mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes) / 1e9, 1)}

    # the kernel call that program makes 26 times a sweep, by itself: one
    # document-tile run (1,381 chunks, their metadata prefetched into
    # SMEM) against the whole doc table and the whole word-topic table
    # (it addresses the resident half-slice in it), both aliased; two
    # K = 1000 count tiles in and out, double-buffered, under the raised
    # VMEM limit
    from harp_tpu.ops import lda_kernel

    i32, f32 = jnp.int32, jnp.float32
    one = jax.sharding.SingleDeviceSharding(devices[0])
    run = jax.jit(functools.partial(
        lda_kernel.cgs_run_update, alpha=0.1, beta=0.01, vbeta=1e4,
        d_tile=512, w_tile=512, ndk_count_bound=7000,
        nwk_count_bound=210_000), donate_argnums=(0, 1)).lower(*[
            jax.ShapeDtypeStruct(shape, dt, sharding=one)
            for shape, dt in (
                ((1000, 6656), f32), (table[0], f32),
                ((1000,), f32), *[((1381, lda_kernel.CHUNK), i32)] * 3,
                ((1381,), i32), ((), i32), ((2,), i32))]).compile()
    out["lda_run"] = {
        "mosaic_calls": run.as_text().count(chip_smoke.MOSAIC_CALL),
        "vmem_estimate_mb": round(lda_kernel.vmem_bytes(
            1000, 512, 512, lda_kernel.CHUNK, 4, 2, 3) / 2 ** 20, 1),
        "temp_mb": round(run.memory_analysis().temp_size_in_bytes
                         / 2 ** 20, 1)}

    # the benchmark's cell mlp-epochs (perf/configs/mlp-mnist8m-b2k): one
    # chip, one worker's 2,023,424 x 784 float32 rows resident, blocks of
    # 32 epochs of 988 SGD steps of 2,048 samples as one program, every
    # MLPConfig field at its default.  What the chip must hold: the table
    # and whatever copy of it the compiler hoists out of the scan.
    from harp_tpu.models import mlp
    from jax.sharding import PartitionSpec as P

    mcfg = mlp.MLPConfig()
    n, d, bpw = 2_023_424, mcfg.sizes[0], 2048

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=mesh.replicated()), tree)

    params = shapes(jax.eval_shape(
        lambda: mlp.init_params(mcfg, jax.random.key(0))))
    epochs_fn, tx = mlp.make_epoch_fn(mesh, mcfg, bpw, n // bpw, 32)
    compiled = epochs_fn.lower(
        params, shapes(jax.eval_shape(tx.init, params)),
        jax.ShapeDtypeStruct((n, d), jnp.float32,
                             sharding=mesh.sharding(mesh.spec(0, ndim=2))),
        jax.ShapeDtypeStruct((n,), jnp.int32,
                             sharding=mesh.sharding(mesh.spec(0, ndim=1))),
        jax.ShapeDtypeStruct((2,), jnp.uint32,
                             sharding=mesh.sharding(P()))).compile()
    mem, hlo = compiled.memory_analysis(), compiled.as_text()
    out["mlp_cell"] = {
        "table_bytes": n * d * 4,
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "table_sized_writes": table_sized_writes(hlo, {n * d}),
        "gathers_and_scatters": gathers_and_scatters(hlo),
        "mosaic_calls": hlo.count(chip_smoke.MOSAIC_CALL)}

    # the benchmark's cell subgraph-colorings (perf/configs/
    # subgraph-orkut-u5): one chip, the configuration's 3,072,441 vertices
    # at 128 padded slots, the 54,903,737 tail entries every seed stages,
    # one block of `trial_chunk` colourings of u5-tree drawn in the program,
    # the padded part summed by the plan `set_graph` makes of the
    # configuration's degree sequence.  What the chip must hold: the
    # resident graph and its degree order (arguments) and the dynamic
    # program's tables and gather tiles (temporaries).
    from harp_tpu.models import subgraph
    from perf import graph_like
    from perf import spec as perf_spec

    scfg = perf_spec.load_json(os.path.join(
        ROOT, "perf", "configs", "subgraph-orkut-u5.json"))
    n, deg = scfg["data"]["n_vertices"], scfg["knobs"]["max_degree"]
    chunk = scfg["knobs"]["trial_chunk"]
    degrees = graph_like.degree_sequence(scfg["data"])
    plan = subgraph.degree_plan(np.sort(np.minimum(degrees, deg))[None], deg)
    # the tail's rows by their entries, as `_tail_rows` stages them: every
    # vertex's entries past 128 in rows of 128, the last partial
    past = np.maximum(degrees - deg, 0)
    tail_plan = subgraph.degree_plan(np.sort(np.concatenate(
        [np.full(int((past // deg).sum()), deg),
         past[past % deg > 0] % deg]))[None], deg)
    tail = tail_plan[-1][1]
    def subgraph_block():
        subgraph._FN_CACHE.clear()  # a program is traced under its names
        return subgraph.make_colorful_count_fn(
            subgraph.TEMPLATES[scfg["knobs"]["template"]],
            scfg["knobs"]["n_colors"], mesh, scfg["knobs"]["overflow_algo"],
            draw_trials=chunk, plan=plan, tail_plan=tail_plan).lower(
                sds((n, deg), jnp.int32), sds((n, deg), jnp.float32),
                sds((tail, deg), jnp.int32), sds((tail,), jnp.int32),
                sds((tail, deg), jnp.float32), sds((n,), jnp.int32),
                (jax.ShapeDtypeStruct((2,), jnp.uint32,
                                      sharding=mesh.replicated()),
                 jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=mesh.replicated()))).compile()

    started = time.perf_counter()
    compiled = subgraph_block()
    compile_s = time.perf_counter() - started
    mem, hlo = compiled.memory_analysis(), compiled.as_text()
    with _without_scopes():
        bare = subgraph_block().as_text()
    out["subgraph_cell"] = {
        "trial_chunk": chunk,
        "plan": plan,
        "tail_plan": tail_plan,
        "compile_s": round(compile_s, 1),
        "padded_part_loops": padded_part_loops(hlo),
        "tail_loops": tail_loops(hlo),
        "resident_bytes": 8 * n * deg + (8 * deg + 4) * tail + 4 * n,
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "gathers": len(re.findall(r" = \S+ gather\(", hlo)),
        "scatters": len(re.findall(r" = \S+ scatter\(", hlo)),
        "loops": hlo.count(" while("),
        "scopes": scopes_in(hlo, "subgraph"),
        "scopes_without": scopes_in(bare, "subgraph"),
        "scopes_change_names_only": program_alone(hlo) == program_alone(bare),
        "largest_gathered": max(
            math.prod(int(d) for d in dims.split(","))
            for dims in re.findall(r" = f32\[([\d,]+)\]\S* gather\(", hlo)),
        "mosaic_calls": hlo.count(chip_smoke.MOSAIC_CALL)}

    # every builder in the registry through the real Mosaic compiler
    out["registry"] = {
        name: mosaic_calls(jax.jit(fn), [
            jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                 sharding=one) for a in args])
        for name, (fn, args) in ((n, KERNELS[n]()) for n in sorted(KERNELS))}
    return out


@pytest.fixture(scope="module")
def compiled():
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], cwd=ROOT,
        # off the chip interpret mode is the default; this is the documented
        # switch for compiling the Mosaic path without executing it
        env={**os.environ, "HARP_PALLAS_FORCE_MOSAIC": "1"},
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_client_is_a_v5e_2x2(compiled):
    assert compiled["device_kind"] == "TPU v5 lite"
    assert compiled["n_devices"] == 4


@pytest.mark.parametrize("n_dev", [1, 4])
def test_full_width_programs_compile_for_v5e(compiled, n_dev):
    progs = compiled[f"full_width_{n_dev}"]
    assert set(progs) == {"kmeans.xla_f32", "kmeans.pallas_int8",
                          "kmeans.xla_int8", "mfsgd.pallas", "mfsgd.dense"}
    # a Mosaic call in each Pallas program, none in its XLA twin
    for name, calls in progs.items():
        assert (calls > 0) == ("pallas" in name), (name, calls)


def test_mfsgd_cell_epochs_compile_for_v5e(compiled):
    """~134k chunks a half-slice, u_bound 553,984, rank 64: the
    scalar-prefetch (SMEM) budget at the benchmark cell's real size."""
    assert compiled["mfsgd_cell"] == {"u_bound": 553_984, "mosaic_calls": 1}


def test_lda_cell_sweep_compiles_for_v5e_and_fits(compiled):
    """The 4 GB word-topic table at the benchmark cell's real size: the
    state is donated (the output takes the table's place, so the aliased
    bytes are the table's and the little beside it: the doc-topic table,
    the chain), and the program holds the table ONCE: 4.1 GB of
    arguments and half a megabyte of temporaries as this client counts
    them, since the tables stay topic-major between sweeps and one buffer
    goes through the rotation (PR 35).  Before, the sweep's temporaries
    were 10.1 GB beside them (14.2 GB; 14.8 GB with the fixed-width
    entries before PR 32), and without the donation it asked for 18.7 GB
    and the chip refused it at its first block (my chip runs, PRs 27 and
    29).  One Mosaic call: the rotation stays a loop over its steps."""
    cell = compiled["lda_cell"]
    assert cell["mosaic_calls"] == 1
    assert cell["table_shape"] == [1000, 2 * 500_224]  # topic-major
    assert cell["table_bytes"] == 2 * 500_224 * 1000 * 4
    assert cell["table_bytes"] <= cell["aliased_bytes"] \
        < 1.02 * cell["table_bytes"]
    assert cell["temp_bytes"] < 64 << 20
    assert cell["held_gb"] < 4.5


@pytest.mark.parametrize("cell,scopes", [
    ("lda_cell", ["lda.chain", "lda.kernel", "lda.keys", "lda.nk",
                  "lda.rotate", "lda.slices", "lda.touched"]),
    ("subgraph_cell", [
        "subgraph.allgather", "subgraph.convolve", "subgraph.count",
        "subgraph.draw", "subgraph.order.put", "subgraph.order.take",
        "subgraph.padded", "subgraph.singleton", "subgraph.sum.leaf",
        "subgraph.sum.t3", "subgraph.tail", "subgraph.tail.add",
        "subgraph.tail.rows"]),
])
def test_cell_programs_scopes_change_names_only_on_v5e(compiled, cell,
                                                      scopes):
    """The cell's program compiled for the chip with its
    ``jax.named_scope``s and with none: every scope reaches the optimized
    text, and the two are the same instructions in the same order
    (:func:`program_alone` says what besides ``metadata={...}`` follows a
    name on this compiler).  ``tests/test_opscopes.py`` holds the five
    programs to the letter on the CPU."""
    got = compiled[cell]
    assert got["scopes"] == scopes and got["scopes_without"] == []
    assert got["scopes_change_names_only"] is True


def test_program_alone_reads_an_hlo_text():
    a = ('HloModule jit_f\n\nFileNames\n1 "a.py"\n\nStackFrames\n'
         '1 {file_location_id=1 parent_frame_id=1}\n\n'
         'ENTRY %main.3 (p.1: f32[8]) -> f32[8] {\n'
         '  %p.1 = f32[8]{0} parameter(0), metadata={op_name="x"}\n'
         '  %lda.kernel.4 = f32[8]{0} custom-call(%p.1), '
         'custom_call_target="tpu_custom_call", backend_config={"custom_'
         'call_config": {"body": "QUJD"}}, metadata={op_name="a/lda.kernel/'
         'pallas_call" stack_frame_id=1}\n'
         '  ROOT %add.7 = f32[8]{0} add(%lda.kernel.4, %p.1), '
         'metadata={op_name="jit(f)/lda.nk/add"}\n}\n')
    b = ('HloModule jit_f\n\n'
         'ENTRY %main.3 (p.1: f32[8]) -> f32[8] {\n'
         '  %p.1 = f32[8]{0} parameter(0)\n'
         '  %closed_call.9 = f32[8]{0} custom-call(%p.1), '
         'custom_call_target="tpu_custom_call", backend_config={"custom_'
         'call_config": {"body": "REVG"}}\n'
         '  ROOT %add.2 = f32[8]{0} add(%closed_call.9, %p.1)\n}\n')
    assert metadata_stripped(a) != metadata_stripped(b)
    assert program_alone(a) == program_alone(b)
    assert "FileNames" not in metadata_stripped(a) \
        and "op_name" not in metadata_stripped(a)
    # another opcode, or another operand, is another program
    assert program_alone(a) != program_alone(b.replace("add(", "subtract("))
    assert program_alone(a) != program_alone(
        b.replace("add(%closed_call.9, %p.1)", "add(%p.1, %p.1)"))


def test_lda_cell_sweep_copies_no_table(compiled):
    """Inside the sweep no XLA op writes a buffer of the table's or of a
    half-slice's element count: nothing transposes the table, cuts it
    into half-slices or joins them, and the resident half-slice is not
    copied around the run scan (13 such instructions before PR 35, nine
    ops and a third of the sweep on the chip: PERF.md section 6)."""
    assert compiled["lda_cell"]["table_sized_writes"] == []


def test_mlp_cell_epochs_compile_for_v5e_and_fit(compiled):
    """The 32-epoch program of ``mlp-epochs`` at the cell's shapes: the
    6.35 GB float32 table is an argument, and ``memory_analysis()`` says
    a copy of it IS made: 3.6 GB of temporaries, one table-sized write
    outside the scan (the bf16 copy the dots read, its 784 columns padded
    to 896: XLA hoists the convert out of the loop, as it does in
    KMeans).  9.98 GB in all, under 12; no Mosaic call (PERF.md section
    5 has what the chip read)."""
    cell = compiled["mlp_cell"]
    assert cell["mosaic_calls"] == 0
    assert cell["table_bytes"] <= cell["argument_bytes"] \
        < 1.01 * cell["table_bytes"]
    assert (cell["argument_bytes"] + cell["temp_bytes"]) / 1e9 < 12.0
    assert 0.5 * cell["table_bytes"] <= cell["temp_bytes"] \
        < 0.6 * cell["table_bytes"]
    assert len(cell["table_sized_writes"]) == 1


def test_mlp_cell_epochs_gather_nothing(compiled):
    """The step picks the label's logit with a select over the class
    columns: the 32-epoch program holds no ``gather`` and no ``scatter``.
    With optax's ``take_along_axis`` it held one gather, ``f32[2048]`` out
    of ``f32[2048,10]``, a third of the step on the chip (PERF.md section
    6, PR 37)."""
    assert compiled["mlp_cell"]["gathers_and_scatters"] == 0


def test_subgraph_cell_block_compiles_for_v5e_and_fits(compiled):
    """One block of ``subgraph-colorings`` at the cell's shapes (com-Orkut's
    3,072,441 vertices, 8 colourings): the 3.9 GB resident graph (the
    padded part, the tail's 718,773 rows of 128 slots with their owners)
    and its 12 MB degree order are the arguments; the tables of the
    dynamic program, widened to whole 128-lane rows where they are
    gathered and scattered, and one gather tile at a time are the
    temporaries (5.8 GB; 6.8 GB while the tail was a scatter-add of
    524,288 entries a tile), and the executable holds under 12 GB in
    all.  Two distinct sub-templates are summed over neighbours (the
    leaf once, not three times), each over the plan's 16 segments and the
    tail plan's 16, every one a loop over tiles: 64 loops; a padded
    segment's loop gathers its tile's rows of ``nbr`` and of ``msk`` and
    the table's rows and sets the sums back into vertex order, a tail
    segment's slices its tile's rows, gathers the table's and adds the
    sums to their owners: 128 gathers, 64 scatters.  The largest
    gathered intermediate is a tile's 4,091 x 128 rows of 128 lanes
    (under the 256 MiB a tile may take) and not ``[n, 128, columns]``.
    It compiles here in 26-30 s (17.5-21.6 s with 34 loops, PR 39; 34-36
    s on the chip's host, PERF.md section 6, PR 41)."""
    cell = compiled["subgraph_cell"]
    assert cell["mosaic_calls"] == 0
    assert cell["resident_bytes"] <= cell["argument_bytes"] \
        < 1.001 * cell["resident_bytes"]
    assert cell["resident_bytes"] > 3.8e9 and cell["trial_chunk"] == 8
    assert (cell["argument_bytes"] + cell["temp_bytes"]) / 1e9 < 12.0
    assert cell["temp_bytes"] < 6.5e9
    assert (cell["gathers"], cell["scatters"], cell["loops"]) == (128, 64, 64)
    assert cell["largest_gathered"] == 4091 * 128 * 128 < (256 << 20) // 4
    assert 5 < cell["compile_s"] < 240


def test_subgraph_cell_block_gathers_the_plans_slots(compiled):
    """Every segment of the plan is one loop in each of the two neighbour
    sums, over tiles of ``[rows, width]`` neighbour ids, ``width`` the
    segment's and the rows ``_segment_tile``'s (as many as 256 MiB and
    32,768 leave room for, evened out over the segment's tiles, the ids
    no multiple of 8 groups of 128): the ids those loops gather, tile by
    tile (the last tile of a loop is moved back and gathers some rows
    again), are the plan's 188,526,608 slots and under 0.1% more, where
    the whole width's are 393,272,448."""
    from harp_tpu.models import subgraph

    cell = compiled["subgraph_cell"]
    plan = [tuple(seg) for seg in cell["plan"]]
    assert len(plan) == 16 and subgraph.plan_slots(plan) == 188_526_608
    want = [[stop - start, subgraph._segment_tile(
        stop - start, width, subgraph._gather_tiles(width, 128)[0]), width]
        for start, stop, width in plan]
    assert sorted(cell["padded_part_loops"]) == sorted(want + want)
    assert [t for _, t, _ in want][:3] + [want[-1][1]] == [
        27_712, 32_728, 16_288, 4_090]
    gathered = sum(-(-rows // tile) * tile * width
                   for rows, tile, width in cell["padded_part_loops"])
    assert 2 * 188_526_608 <= gathered < 1.001 * 2 * 188_526_608


def test_subgraph_cell_block_gathers_the_tail_plans_slots(compiled):
    """The tail likewise: every segment of its plan one loop in each of
    the two sums, over tiles of ``[rows, width]`` ids sliced from the
    staged tail rows, the rows ``_segment_tile``'s: 56,582,336 slots a sum
    and under 1% more for the last tiles moved back (masked, not added
    twice), where the flat tail's 105 tiles scatter-added 54,903,737
    entries one by one."""
    from harp_tpu.models import subgraph

    cell = compiled["subgraph_cell"]
    plan = [tuple(seg) for seg in cell["tail_plan"]]
    assert len(plan) == 16 and subgraph.plan_slots(plan) == 56_582_336
    assert plan[-1][1] == 718_773
    want = [[subgraph._segment_tile(
        stop - start, width, subgraph._gather_tiles(width, 128)[0]), width]
        for start, stop, width in plan]
    assert sorted(cell["tail_loops"]) == sorted(want + want)
    gathered = sum(-(-(stop - start) // tile) * tile * width
                   for (start, stop, width), (tile, _) in zip(plan, want))
    assert 56_582_336 <= gathered < 1.01 * 56_582_336


def test_padded_part_loops_reads_an_hlo_text():
    hlo = """
%gathers (p: f32[100,128], i: s32[8,16]) -> f32[8,16,128] {
  ROOT %gather.1 = f32[8,16,128]{2,1,0} gather(%p, %i), offset_dims={2}
}

%body.1 (arg: (s32[], s32[50], f32[100,128])) -> (s32[], s32[50]) {
  %fusion.1 = f32[8,16,128]{2,1,0} fusion(%p, %i), kind=kCustom, calls=%gathers
}

%tail (arg: (s32[], f32[100,128])) -> (s32[]) {
  %gather.2 = f32[64,128]{1,0} gather(%p, %j), offset_dims={1}
}

ENTRY %main () -> f32[] {
  %while.1 = (s32[], s32[50]{0}, f32[100,128]{1,0}) while(%t), condition=%c, body=%body.1
  %while.2 = (s32[], f32[100,128]{1,0}) while(%u), condition=%c, body=%tail
}
"""
    assert padded_part_loops(hlo) == [[50, 8, 16]]
    assert tail_loops(hlo) == []
    named = hlo.replace(
        "body=%body.1", 'body=%body.1, metadata={op_name="jit(f)/'
        'subgraph.sum.t3/subgraph.tail/while"}')
    assert padded_part_loops(named) == [] and tail_loops(named) == [[8, 16]]


def test_gathers_and_scatters_reads_an_hlo_text():
    hlo = """
  %gather.13 = f32[2048]{0:T(1024)} gather(%param_0.421, %custom-call.12), offset_dims={}
  ROOT %scatter.2 = f32[8,16]{1,0} scatter(%p, %i, %u), to_apply=%add
  %fusion.131 = f32[2048]{0:T(1024)S(1)} fusion(%fusion.129), kind=kCustom, calls=%gather_computation
  %all-gather.1 = f32[8,16]{1,0} all-gather(%p), dimensions={0}
"""
    assert gathers_and_scatters(hlo) == 2


def test_table_sized_writes_reads_an_hlo_text():
    hlo = """
  %p = f32[8,16]{1,0:T(8,128)} parameter(0), sharding={replicated}
  %copy.1 = f32[16,8]{1,0:T(8,128)} copy(%p)
  %fusion.2 = f32[2,4,8]{2,1,0} fusion(%copy.1), kind=kLoop
  %gte = f32[8,16]{1,0} get-tuple-element(%w), index=1
  %call = (f32[8,16]{1,0}, s32[4]{0}) custom-call(%p), custom_call_target="tpu_custom_call"
  %k = f32[8,16]{1,0} custom-call(%p), custom_call_target="tpu_custom_call"
  %other = f32[8,16]{1,0} custom-call(%p), custom_call_target="Sharding"
  ROOT %small = f32[4,8]{1,0} slice(%copy.1)
"""
    assert table_sized_writes(hlo, {128}) == [
        ["copy.1", "copy"], ["other", "custom-call"]]
    assert table_sized_writes(hlo, {128, 64}) == [
        ["copy.1", "copy"], ["fusion.2", "fusion"],
        ["other", "custom-call"]]
    assert table_sized_writes(hlo, {32}) == [["small", "slice"]]


def test_lda_run_kernel_compiles_for_v5e(compiled):
    """``cgs_run_update`` by itself at the cell's shapes: 1,381 chunks of
    128 slots a document-tile run, K = 1000, 512-wide tiles, 2 and 3
    gather planes.  Its VMEM estimate passes the default 16 MiB scoped
    limit (which is why the call raises it) and stays under the budget
    the kernel holds itself to; both tables go through in place, so the
    call holds no second table."""
    from harp_tpu.ops import lda_kernel

    run = compiled["lda_run"]
    assert run["mosaic_calls"] == 1
    assert 16 < run["vmem_estimate_mb"] < lda_kernel._VMEM_BUDGET / 2 ** 20
    assert run["temp_mb"] < 64  # no copy of a table beside the aliased one


def test_registered_kernels_compile_for_v5e(compiled):
    from harp_tpu.ops.kernel_registry import KERNELS

    assert set(compiled["registry"]) == set(KERNELS)
    assert all(calls > 0 for calls in compiled["registry"].values())


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    print(json.dumps(_compile_all()))
