"""``correct`` on the LDA cells, through the cell's own driver at a toy
size on the CPU: check (c) is taken where the chain stood after the
configuration's ``reference.chain_sweeps`` sweeps, however long the run
went on, and every planted fault of ``lda_faults.py`` is refused by the
part that is named for it, once through the driver sweep by sweep and once
through a whole run of the harness.

Read at this size (CPU, PR 31; seeds 11, 12, 13, 2147484001; 20,000
tokens, 16 topics): the program 0.011-0.027 from the mean of the plain
sampler's four keys at sweep 4, the band 0.058-0.061 (0.6 of a step of
0.096-0.102), the keys 0.011-0.028 apart; a sampler fed ``N_wk = 0``
0.104-0.157; one that moves nothing 0.90-0.91.  (Taken after the last of
20 sweeps, as it was before PR 31, the same chain read up to 0.29 of a
step with the keys 0.72 apart: a chain that has flattened.)"""

import copy
import functools
import json
import os

import jax
import numpy as np
import pytest

import lda_faults
from perf import harness, spec
from test_harness import TINY, checkout  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lda-sweeps"
S = spec.Cell(ROOT, CELL).config["reference"]["chain_sweeps"]
SEEDS = (11, 12, 13, 2147484001)
LONG = 20


def _driver(seed=11, blocks=LONG, fault=None, **data):
    """The cell's driver at the toy size after ``blocks`` sweeps, with a
    fault of ``lda_faults`` planted under it."""
    cell = spec.Cell(ROOT, CELL)
    config = copy.deepcopy(cell.config)
    config["data"].update(TINY["lda"]["data"], **data)
    config["knobs"].update(TINY["lda"]["knobs"])
    module = cell.driver_module()
    if fault:
        lda_faults.FAULTS[fault](module.Driver)
    driver = module.Driver(config, {**cell.traffic, "steps": 1},
                           jax.devices()[:1], seed, harness.Recorder())
    driver.setup()
    for _ in range(blocks):
        items, ok = driver.block()
        assert ok and items == config["data"]["n_tokens"]
    return driver


@functools.cache
def _verdict(seed, blocks, fault=None):
    return _driver(seed, blocks, fault).check()


def _exact(verdict):
    return [verdict[k] for k in ("count_mismatches", "row_sum_mismatches",
                                 "nk_mismatches", "nk_total_off")]


def _in_band(verdict):
    return verdict["ll_chain_abs"] <= verdict["ll_chain_abs_limit"]


def test_the_fixed_point_is_inside_what_every_run_reaches():
    """A traced run of the cell holds the warm-up and three blocks."""
    assert S == 4
    assert spec.Cell(ROOT, CELL).traffic["steps"] == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_a_long_run_is_correct_and_reads_as_a_run_that_stopped_at_s(seed):
    """(c) does not know how long the window was: 20 sweeps and 4 print
    the same distance and the same band."""
    long, short = _verdict(seed, LONG), _verdict(seed, S)
    assert long["correct"] and short["correct"], (long, short)
    assert (long["sweeps"], short["sweeps"]) == (LONG, S)
    assert long["chain_sweeps"] == short["chain_sweeps"] == S
    for name in ("ll_chain_abs", "ll_chain_abs_limit", "ll_of_chain",
                 "ll_plain", "ll_plain_step", "ll_plain_key_range"):
        assert long[name] == short[name], name
    # (a) and (b) stay on the final state, whatever sweep that is
    assert _exact(long) == [0, 0, 0, 0] and long["ll_tables_rel"] <= 1e-6
    assert long["ll_of_tables"] > long["ll_of_chain"] == pytest.approx(
        short["ll_of_tables"], abs=1e-6)
    # narrower than a sweep's step, wider than the plain keys stand apart
    assert long["ll_plain_key_range"] < long["ll_chain_abs_limit"] \
        < long["ll_plain_step"]


@pytest.mark.parametrize("fault,part", [
    ("zero_nwk", "band"), ("frozen", "band"), ("one_topic", "recount")])
def test_faults_fail_by_the_same_part_after_a_long_run(fault, part):
    """PR 29's three planted faults, the run gone on to 20 sweeps."""
    verdict = _verdict(11, LONG, fault)
    assert not verdict["correct"]
    if part == "band":  # the books are exact, the draws are not
        assert _exact(verdict) == [0, 0, 0, 0]
        assert verdict["ll_tables_rel"] <= 1e-6
        assert verdict["ll_chain_abs"] > 1.5 * verdict["ll_chain_abs_limit"]
    else:  # the old and the new topic, in both tables; no row sum
        assert _exact(verdict) == [4, 0, 0, 0]
        assert _in_band(verdict)
    if fault == "frozen":
        assert verdict["ll_of_chain"] == pytest.approx(
            verdict["ll_initial"], abs=1e-5)
        assert verdict["ll_chain_abs"] > verdict["ll_plain_step"]


def test_tables_through_bfloat16_fail_the_recount_alone():
    """The nearest precision below the configuration's: (c) is taken on
    counts rebuilt from the chain and cannot see a table's rounding."""
    driver = _driver(blocks=S + 2, fault="bf16_tables",
                     n_tokens=60_000)  # the hottest word passes 256
    assert float(driver.model.Nwk.max()) > 256
    verdict = driver.check()
    assert not verdict["correct"]
    assert verdict["count_mismatches"] > 0 and _in_band(verdict)


def test_the_kept_chain_survives_the_next_blocks_donation():
    driver = _driver(blocks=S - 1)
    assert driver.kept is None
    driver.block()
    sweeps, kept = driver.kept
    assert sweeps == S == driver.sweeps
    assert kept is not driver.model.z_grid
    np.testing.assert_array_equal(kept, driver.model.z_grid)
    as_kept = np.asarray(kept).copy()
    for _ in range(2):  # each donates the live chain
        driver.block()
    assert driver.kept[1] is kept and not kept.is_deleted()
    np.testing.assert_array_equal(kept, as_kept)
    assert (np.asarray(driver.model.z_grid) != as_kept).mean() > 0.1
    assert driver.check()["chain_sweeps"] == S


@pytest.mark.parametrize("blocks", [1, S - 1])
def test_a_run_shorter_than_s_is_compared_at_its_last_sweep(blocks):
    verdict = _verdict(12, blocks)
    assert verdict["correct"]
    assert verdict["chain_sweeps"] == verdict["sweeps"] == blocks
    assert verdict["ll_of_chain"] == pytest.approx(verdict["ll_of_tables"],
                                                   abs=1e-6)


@pytest.mark.parametrize("fault,correct", [
    ("zero_nwk_late", True), ("zero_nwk_late_books_kept", False)])
def test_a_fault_planted_after_s_is_not_the_bands_to_catch(fault, correct):
    """What (c) no longer covers, stated: a sampler that goes wrong only
    after sweep ``S`` (here: fed ``N_wk = 0`` from sweep 5 on) passes (c),
    and with its books redone it passes ``correct``.  What holds the later
    sweeps is their bookkeeping: left as that sampler leaves them, the
    tables fail the exact recount (a) and the likelihood of the tables
    (b), and every block's count of touched tokens holds that each token
    was resampled."""
    verdict = _verdict(13, LONG, fault)
    assert _in_band(verdict)
    assert verdict["ll_chain_abs"] == _verdict(13, LONG)["ll_chain_abs"]
    assert verdict["correct"] is correct
    if not correct:
        assert verdict["count_mismatches"] > 1000
        assert not verdict["ll_tables_rel"] <= verdict["ll_tables_rel_limit"]


# -- a whole run of the harness, the look for a chip skipped -------------

@pytest.mark.parametrize("fault,failing", [
    (None, set()), ("zero_nwk", {"ll_chain_abs"}),
    ("frozen", {"ll_chain_abs"}), ("one_topic", {"count_mismatches"})])
def test_a_run_with_the_timed_path_broken_is_not_correct(
        fault, failing, checkout, capfd):  # noqa: F811
    lines = []
    with lda_faults.planted(fault):
        out = harness.run_cell(checkout, CELL, seed=2147484001, seconds=0.3,
                               trace=False, require_platform=None,
                               override=TINY["lda"], say=lines.append)
    assert out["correct"] is (fault is None) and out["failed"] == 0
    over = {k for k, c in out["compared"].items()
            if not c["value"] <= c["limit"]}
    # one topic of 20,000 moves the toy's likelihood of the tables too
    assert failing <= over <= failing | ({"ll_tables_rel"} if fault ==
                                         "one_topic" else set())
    check = json.loads(lines[0][len("info "):])["check"]
    assert check["chain_sweeps"] == S < check["sweeps"] == \
        out["attempted"] + 1
    # each compared number beside its limit: standard error's last lines
    err = capfd.readouterr().err.strip().splitlines()
    assert err[-len(out["compared"]):] == [
        f"compared {k} {c['value']} limit {c['limit']}"
        for k, c in out["compared"].items()]
