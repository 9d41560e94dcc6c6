"""The topology-aware collective planner (PR 11, harp_tpu/plan).

Pins, in order: the topology price list's algebra; the frozen plan-row
vocabularies' sync with scripts/check_jsonl.py (invariant 10 stays a
standalone mirror, like the lint rule ids); the acceptance criterion —
planner-predicted per-site bytes equal the CommGraph byte sheets
EXACTLY for every registered program; fail-closed decisions (schedule
is always "keep"; candidates only where the topology predicts a real
win AND a named config can measure it); and the plan CLI's stamped,
invariant-10-clean JSON rows.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts"))

import check_jsonl  # noqa: E402
from harp_tpu.plan import planner, topology  # noqa: E402


# -- topology ---------------------------------------------------------------

def test_topology_validation_and_classes():
    with pytest.raises(ValueError, match="group into hosts"):
        topology.Topology("x", 8, 3, 10.0, 10.0)
    with pytest.raises(ValueError, match="positive"):
        topology.Topology("x", 8, 8, 0.0, 10.0)
    t = topology.v4_32()
    assert t.hosts == 4 and t.n_workers == 16
    assert t.rates_source == "declared"


def test_single_chip_prices_zero_wire():
    t = topology.single_chip()
    assert t.wire_bytes("psum", 1024) == 0.0
    assert t.cost_s("ppermute", 1024) == 0.0


def test_ring_cost_algebra():
    """bytes × hops / rate: the sim ring's psum moves 2(n-1)/n of the
    payload at the intra rate; amplification multiplies linearly."""
    t = topology.sim_ring(8)
    b = 1000
    expect = b * 2 * 7 / 8 / (10.0 * 1e9)
    assert abs(t.cost_s("psum", b) - expect) < 1e-18
    assert abs(t.cost_s("psum", b, amplification=3)
               - 3 * expect) < 1e-18
    with pytest.raises(ValueError, match="unknown collective"):
        t.cost_s("send_recv", b)


def test_hier_psum_wins_only_across_hosts():
    """The decision the whole subsystem exists for: on a one-host ring
    the two-stage psum prices >= the one-shot; on v4_32 (4 hosts, slow
    inter class) it prices strictly cheaper."""
    flat, multi = topology.sim_ring(8), topology.v4_32()
    b = 1 << 20
    assert flat.hier_stage_cost_s(b) >= flat.cost_s("psum", b) * 0.999
    assert multi.hier_stage_cost_s(b) < multi.cost_s("psum", b)


def test_detect_names_the_sim_ring(mesh):
    t = topology.detect(mesh)
    assert t.name == "sim_ring_8" and t.n_workers == 8


def test_probed_rates_stamp(mesh):
    t = topology.probed(topology.sim_ring(8), mesh, size_mb=0.5)
    assert t.rates_source == "probed" and t.intra_gbs > 0


# -- frozen vocabulary sync pins (check_jsonl stays standalone) -------------

def test_plan_vocabularies_in_sync():
    assert tuple(planner.SCHEDULES) == check_jsonl.KNOWN_PLAN_SCHEDULES
    assert tuple(topology.TOPOLOGY_NAMES) == \
        check_jsonl.KNOWN_PLAN_TOPOLOGIES
    # the frozen byte-scaling math must agree for every schedule on
    # awkward (odd, tiny, huge) sheet sizes
    for sched in planner.SCHEDULES:
        for b in (0, 1, 3, 7, 1060, 131072, 10**9 + 7):
            assert planner.predicted_bytes(sched, b) == \
                check_jsonl._plan_predicted_bytes(sched, b), (sched, b)


def test_flip_candidates_name_registered_programs_and_configs():
    """Every candidate the planner can name is a config name the
    checker admits in a row, on a registered driver program."""
    for cfg in planner.FLIP_CANDIDATE_CONFIGS.values():
        assert cfg in check_jsonl.KNOWN_MODEL_CONFIGS, cfg
    from harp_tpu.analysis.drivers import DRIVERS

    for prog, _, _ in planner.FLIP_CANDIDATE_CONFIGS:
        assert prog in DRIVERS, prog


# -- the acceptance criterion: predictions == byte sheets -------------------

def test_predicted_bytes_match_byte_sheets_for_all_programs(mesh):
    """Plan every registered program and check each site's fail-closed
    prediction equals the CommGraph byte sheet's amplified bytes for
    that site, exactly — and the plan total equals the sheet total."""
    from harp_tpu.analysis import commgraph
    from harp_tpu.analysis.drivers import DRIVERS

    topo = topology.detect(mesh)
    for name in sorted(DRIVERS):
        fn, args = DRIVERS[name]()
        graph = commgraph.extract(name, fn, args)
        plan = planner.plan_sheet(
            name, {"collectives": [s.row() for s in graph.sites]}, topo)
        sheet_by_site = {}
        for s in graph.sites:
            key = (s.site, s.primitive)
            sheet_by_site[key] = sheet_by_site.get(key, 0) + \
                s.per_shard_bytes * max(s.amplification, 1)
        got_by_site = {}
        for d in plan.sites:
            key = (d.site, d.primitive)
            got_by_site[key] = got_by_site.get(key, 0) + d.predicted_bytes
        assert got_by_site == sheet_by_site, name
        assert plan.predicted_bytes_total() == graph.amplified_bytes(), \
            name


def test_every_decision_fails_closed(mesh):
    """No topology — not even one where every alternative wins — may
    change a chosen schedule: 'keep' is the only choice; alternatives
    surface exclusively as flip candidates."""
    for topo in (topology.sim_ring(8), topology.v4_32(),
                 topology.single_chip()):
        plans = planner.plan_all(topo)
        assert set(plans) == set(check_jsonl.KNOWN_LINT_PROGRAMS)
        for plan in plans.values():
            for site in plan.sites:
                assert site.schedule == "keep", (plan.program, site.site)
                assert site.predicted_bytes == site.sheet_bytes


def test_candidates_follow_the_topology(mesh):
    """kmeans.fit's hier candidate appears ONLY where the price list
    says it wins (v4_32's slow inter-host class), never on the flat
    ring; the lda wire candidates win everywhere bytes halve."""
    flat = planner.plan_program("kmeans.fit", topology.sim_ring(8))
    multi = planner.plan_program("kmeans.fit", topology.v4_32())
    assert flat.flip_candidates() == []
    assert multi.flip_candidates() == ["kmeans_hier_psum"]

    lda = planner.plan_program("lda.epoch", topology.sim_ring(8))
    assert set(lda.flip_candidates()) == {"lda_planner_wire",
                                          "lda_rotate_int8"}
    (ring_site,) = [s for s in lda.sites if s.verb == "reshard"]
    # the cheapest mapped winner is the headline candidate
    assert ring_site.flip_candidate == "lda_rotate_int8"
    assert ring_site.candidates == {"wire_bf16": "lda_planner_wire",
                                    "wire_int8": "lda_rotate_int8"}


def test_quantized_sites_take_no_second_wire_trade():
    """A site whose ledger wire is already narrow must not be offered a
    wire_* alternative (it took its trade; re-quantizing compounds)."""
    entry = {"site": "x.py:1", "primitive": "ppermute", "verb": "reshard",
             "per_shard_bytes": 1024, "amplification": 4,
             "ledger_wire": "int8"}
    dec = planner.decide_site("lda.epoch", entry, topology.sim_ring(8))
    assert not any(a.startswith("wire_") for a in dec.alternatives)
    assert dec.candidates == {}


def test_plan_program_rejects_unknown_names():
    with pytest.raises(KeyError, match="not a registered driver"):
        planner.plan_program("no.such.program")


# -- the serialized row + CLI -----------------------------------------------

def _stamp(row):
    return {**row, "backend": "cpu", "date": "2026-08-04",
            "commit": "test"}


def test_plan_row_passes_invariant_10(mesh):
    plan = planner.plan_program("mfsgd.epoch", topology.detect(mesh))
    assert check_jsonl._check_plan_row("t", 1, _stamp(plan.row())) == []


def test_cli_emits_stamped_invariant_clean_rows(mesh, capsys):
    from harp_tpu.plan import cli

    rc = cli.main(["--program", "kmeans.fit", "--program", "lda.epoch",
                   "--json", "--topology", "v4_32"])
    assert rc == 0
    rows = [json.loads(ln) for ln in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["program"] for r in rows] == ["kmeans.fit", "lda.epoch"]
    for row in rows:
        assert row["kind"] == "plan" and row["config"] == "plan"
        assert all(k in row for k in ("backend", "date", "commit"))
        assert check_jsonl._check_plan_row("cli", 1, row) == []
    assert rows[0]["flip_candidates"] == ["kmeans_hier_psum"]


def test_cli_rejects_unknown_program(mesh, capsys):
    from harp_tpu.plan import cli

    assert cli.main(["--program", "nope", "--json"]) == 2
