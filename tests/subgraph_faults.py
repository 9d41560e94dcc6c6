"""The toy shape ``subgraph-colorings`` rehearses at on the CPU, and the
seven controls its ``correct`` is held to, planted in the program
(``harp_tpu.models.subgraph``) for the length of a ``with``: the tier-1
cases of ``test_subgraph_cell.py`` plant them at the toy size, and
``python3 tests/subgraph_faults.py <control> <seed> <seconds>`` plants
one at the cell's size on the chip (PERF.md section 6 has what each read
there)."""

import contextlib
import os
import sys
from unittest import mock

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script, on the chip
    sys.path.insert(0, ROOT)

from harp_tpu.models import subgraph as SG  # noqa: E402

# the in-test override (``harness.run_cell(override=...)``): the shape
# only.  300 vertices, 1,500 edges, one hub of 60 neighbours, the padded
# part 8 wide so that a third of the entries ride the tail
TINY = {"data": {"n_vertices": 300, "n_edges": 1500, "degree_max": 60},
        "knobs": {"max_degree": 8, "trial_chunk": 4},
        "work": {"entries": 3000, "vertices": 300},
        "traffic": {"steps": 4, "trace_seconds": 0.2},
        # a toy count is a whole number under 2^24, the same bits from
        # program and reference, so its band is a toy band (bfloat16
        # tables read 1e-4 there)
        "reference": {"counts_rel_limit": 1e-6}}


@contextlib.contextmanager
def _fresh_programs():
    """Programs traced under a control are not found again after it."""
    SG._FN_CACHE.clear()
    try:
        yield
    finally:
        SG._FN_CACHE.clear()


@contextlib.contextmanager
def tail_left_out():
    """The entries past ``max_degree`` are dropped at install."""
    true = SG._partition_overflow
    with mock.patch.object(
            SG, "_partition_overflow",
            lambda overflow, n_pad, nw: true(overflow[:0], n_pad, nw)):
        yield


@contextlib.contextmanager
def hub_truncated():
    """The largest hub's row is cut at ``max_degree``: its tail entries
    go, everyone else's stay."""
    true = SG.pad_csr

    def cut(edges, n_vertices, max_degree):
        nbr, msk, overflow = true(edges, n_vertices, max_degree)
        hub = np.bincount(overflow[:, 0]).argmax()
        return nbr, msk, overflow[overflow[:, 0] != hub]

    with mock.patch.object(SG, "pad_csr", cut):
        yield


@contextlib.contextmanager
def bf16_tables():
    """Every table that is summed over neighbours passes through
    bfloat16."""
    true = SG.C.allgather

    def rounded(tree, **kw):
        # not a pair of casts: XLA may keep the excess precision of a
        # float32 -> bfloat16 -> float32 round trip, and on the chip does
        return true(jax.tree.map(
            lambda t: jax.lax.reduce_precision(
                t, exponent_bits=8, mantissa_bits=7)
            if t.dtype == np.float32 else t, tree), **kw)

    with _fresh_programs(), mock.patch.object(SG.C, "allgather", rounded):
        yield


@contextlib.contextmanager
def wrong_position_map():
    """One entry of a subset-convolution plan names the wrong colour set
    for its child: the star's first term reads the leaf column of a
    colour the root already holds."""
    true = SG._dp_subset_tables

    def bent(tpl, n_colors):
        combos = true(tpl, n_colors)

        def plan(sz1, sz2):
            out = combos(sz1, sz2)
            if (sz1, sz2) == (1, 1):
                S, S1, S2 = out[0]
                out[0] = (S, S1, S1)
            return out

        return plan

    with _fresh_programs(), mock.patch.object(SG, "_dp_subset_tables", bent):
        yield


@contextlib.contextmanager
def four_colours_of_five():
    """Four colours' worth of one-hot where five are stated: the fifth
    colour's columns of every vertex's own table stay empty."""
    true = SG._alone

    def four(colors, k):
        table = true(colors, k)
        keep = table.shape[-1] * (k - 1) // k
        return table * (np.arange(table.shape[-1]) < keep)

    with _fresh_programs(), mock.patch.object(SG, "_alone", four):
        yield


@contextlib.contextmanager
def first_block_redrawn():
    """The draw takes no notice of the block's index: every block counts
    the first block's colourings again."""
    true = SG.block_colors
    with _fresh_programs(), mock.patch.object(
            SG, "block_colors",
            lambda key_bits, block, *shape: true(key_bits, 0 * block,
                                                 *shape)):
        yield


@contextlib.contextmanager
def skewed_after_first():
    """From the second block on, every second vertex is given colour 0
    whatever it drew: colour 0 comes up three times in five, and a
    colourful map a fifth as often."""
    true = SG.block_colors

    def skewed(key_bits, block, n_rows, trials, k):
        colors = true(key_bits, block, n_rows, trials, k)
        half = (np.arange(n_rows) % 2 == 0)[:, None]
        return jax.numpy.where((block > 0) & half, 0, colors)

    with _fresh_programs(), mock.patch.object(SG, "block_colors", skewed):
        yield


CONTROLS = {"tail_left_out": tail_left_out,
            "hub_truncated": hub_truncated,
            "bf16_tables": bf16_tables,
            "wrong_position_map": wrong_position_map,
            "four_colours_of_five": four_colours_of_five,
            "first_block_redrawn": first_block_redrawn,
            "skewed_after_first": skewed_after_first}


if __name__ == "__main__":
    # on the chip, at the cell's size: one control through the harness
    from perf import harness, spec

    control, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    with CONTROLS[control]():
        out = harness.run_cell(ROOT, "subgraph-colorings", seed, seconds,
                               False)
    print("control", control, spec.dumps(out))
    sys.exit(0 if out["correct"] is False else 1)
