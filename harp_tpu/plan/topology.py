"""Mesh topology model — the planner's price list.

Reference parity (SURVEY.md §3.6, ROADMAP "topology-aware collective
planner"): Harp's collective algorithms were chosen by hand per app
(regroup-allgather vs. bidirectional exchange) with no model of the
fabric underneath; TACCL (PAPERS.md arXiv:2111.04867) showed that a
*profiled topology* plus a communication sketch is enough to pick the
schedule per collective, and the portable-redistribution paper
(arXiv:2112.01075) prices redistribution the same way.  This module is
the harp-tpu topology side: a :class:`Topology` names the worker ring,
its host grouping, and two **link classes** (intra-host ICI vs.
inter-host ICI/DCN) with declared-or-probed rates; :meth:`Topology.
cost_s` prices one collective site as ``bytes × hops / rate`` per link
class — deliberately a *ranking* model (which schedule is cheapest
here), not a wall-clock predictor (calibration against chip rows is
ROADMAP Design 6).

Four named instances are frozen into the plan-row vocabulary
(``scripts/check_jsonl.py`` invariant 10 — a plan row naming an unknown
topology is not evidence about this repo's meshes):

- ``single_chip``   — 1 worker; every "wire" is HBM (collectives fold).
- ``sim_ring_8``    — the 8-simulated-CPU-worker test mesh (declared
  loopback rate; absolute numbers meaningless, *ratios* still rank
  schedules identically, which is all the fail-closed planner uses).
- ``v5e_2x2``       — one host of four v5e chips (the four-chip machine
  chip_smoke.py runs on): one link class, declared ICI rate.
- ``v4_32``         — the north-star v4-32 slice: 16 chips over 4 hosts
  (4 chips/host), declared ICI rates with the inter-host class slower
  (the hierarchical-psum win condition).  Rates are DECLARED
  assumptions until :func:`probed` measures them on the mesh, and
  every consumer stamps ``rates_source`` so a declared ranking can
  never masquerade as a measured one.
"""

from __future__ import annotations

import dataclasses

#: the frozen topology-tag vocabulary (check_jsonl invariant 10 pins it)
TOPOLOGY_NAMES = ("single_chip", "sim_ring_8", "v5e_2x2", "v4_32")

#: declared per-chip HBM by topology tag (PR 19, the memory spine's
#: denominator): v4 ships 32 GiB HBM2 per chip (public spec); the CPU
#: sim targets model a v5e-class 16 GiB so headroom_frac is meaningful
#: on the test mesh.  DECLARED, like the link rates — on a chip the
#: memory ledger reads the device's own ``bytes_limit`` instead.
HBM_BYTES_PER_CHIP = {
    "single_chip": 16 << 30,
    "sim_ring_8": 16 << 30,
    "v5e_2x2": 16 << 30,
    "v4_32": 32 << 30,
}


def hbm_bytes(name: str) -> int:
    """Declared per-chip HBM for a topology tag (16 GiB for unknown
    tags, e.g. sim_ring_N test meshes — conservative, never zero)."""
    return HBM_BYTES_PER_CHIP.get(name, 16 << 30)

#: per-worker wire-byte multipliers for a ring lowering of each
#: primitive, as a fraction of the jaxpr operand bytes ``b`` (the byte
#: sheet's ``per_shard_bytes``).  Ring algebra: psum = reduce-scatter +
#: allgather moves 2·b·(n-1)/n; all_gather of a b-byte shard sends it
#: n-1 times; ppermute is one hop; all_to_all keeps (n-1)/n of b on the
#: wire; pmax rides the psum formula (tiny scale exchanges).
_RING_FACTORS = {
    "psum": lambda n: 2.0 * (n - 1) / n,
    "pmax": lambda n: 2.0 * (n - 1) / n,
    "pmin": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: float(n - 1),
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "reduce_scatter": lambda n: (n - 1) / n,
}


@dataclasses.dataclass(frozen=True)
class Topology:
    """One mesh's link-class price list (see module docstring)."""

    name: str                 # frozen tag (TOPOLOGY_NAMES)
    n_workers: int
    workers_per_host: int
    intra_gbs: float          # intra-host link class rate, GB/s
    inter_gbs: float          # inter-host link class rate, GB/s
    rates_source: str = "declared"   # "declared" | "probed"

    def __post_init__(self):
        if self.n_workers < 1 or self.workers_per_host < 1:
            raise ValueError("topology needs >= 1 worker per class")
        if self.n_workers % self.workers_per_host:
            raise ValueError(
                f"{self.n_workers} workers do not group into hosts of "
                f"{self.workers_per_host}")
        if self.intra_gbs <= 0 or self.inter_gbs <= 0:
            raise ValueError("link rates must be positive")

    @property
    def hosts(self) -> int:
        return self.n_workers // self.workers_per_host

    def wire_bytes(self, primitive: str, per_shard_bytes: int,
                   amplification: int = 1) -> float:
        """Per-worker bytes on the wire for one site per program run."""
        factor = _RING_FACTORS.get(primitive)
        if factor is None:
            raise ValueError(f"unknown collective primitive {primitive!r}")
        if self.n_workers == 1:
            return 0.0
        return per_shard_bytes * factor(self.n_workers) * max(
            amplification, 1)

    def cost_s(self, primitive: str, per_shard_bytes: int,
               amplification: int = 1) -> float:
        """Seconds to move one site's wire bytes: bytes × hops / rate
        per link class.  Ring steps run link-concurrently, so a flat
        ring's time is its per-link bytes over the SLOWEST link class
        it crosses — on a multi-host ring the host-boundary links gate
        every step (the hierarchical-schedule win condition); one-host
        rings ride the intra class alone."""
        wire = self.wire_bytes(primitive, per_shard_bytes, amplification)
        if wire == 0.0:
            return 0.0
        rate = (min(self.intra_gbs, self.inter_gbs) if self.hosts > 1
                else self.intra_gbs)
        return wire / (rate * 1e9)

    def hier_stage_cost_s(self, per_shard_bytes: int,
                          amplification: int = 1) -> float:
        """The hierarchical two-stage reduction's price (the bandwidth-
        optimal decomposition this model assumes the grouped-psum
        lowering achieves): stage 1 reduce-scatters inside each host
        (intra class, ring of ``workers_per_host``), stage 2 allreduces
        across hosts with each of the ``workers_per_host`` workers
        carrying its 1/g payload shard over the boundary (inter class),
        stage 3 allgathers intra — so the slow class moves
        ``2·(hosts-1)/hosts · b/g`` instead of the flat ring's full
        ``2·(n-1)/n · b``."""
        b = per_shard_bytes * max(amplification, 1)
        g, h = self.workers_per_host, self.hosts
        intra = (2.0 * (g - 1) / g) * b / (self.intra_gbs * 1e9) if g > 1 \
            else 0.0
        inter = (2.0 * (h - 1) / h) * (b / g) / (self.inter_gbs * 1e9) \
            if h > 1 else 0.0
        return intra + inter


def single_chip() -> Topology:
    """One worker: every collective folds to a copy; HBM-class rate."""
    return Topology("single_chip", 1, 1, intra_gbs=819.0, inter_gbs=819.0)


def sim_ring(n: int = 8) -> Topology:
    """The n-simulated-CPU-worker test ring (tests/conftest.py mesh).
    Declared loopback rate — ratios rank schedules, absolutes are
    meaningless, which the fail-closed planner never forgets."""
    return Topology(f"sim_ring_{n}", n, n, intra_gbs=10.0, inter_gbs=10.0)


def v5e_2x2() -> Topology:
    """One host of four v5e chips on a 2×2 ICI mesh.  DECLARED rate, not
    a measurement (2026-09-26, no probe run): Google Cloud
    documentation, "TPU v5e", gives 1,600 Gbit/s of interconnect per
    chip = 4 links × 50 GB/s; a 1-D worker ring rides one link per
    direction — :func:`probed` before believing absolute seconds."""
    return Topology("v5e_2x2", 4, 4, intra_gbs=50.0, inter_gbs=50.0)


def v4_32() -> Topology:
    """The north-star v4-32 slice: 16 chips over 4 hosts.  DECLARED
    rates, not measurements (2026-08-04, no chip touched: ~45 GB/s/dir
    intra-host ICI from the public v4 ICI spec, ~25 GB/s effective
    across the host-boundary torus links — the BASELINE.md scaling
    section's assumption class) — :func:`probed` before believing
    absolute seconds."""
    return Topology("v4_32", 16, 4, intra_gbs=45.0, inter_gbs=25.0)


def detect(mesh=None) -> Topology:
    """The topology of the ACTIVE mesh: single_chip for one device, the
    sim ring for the CPU backend, v5e_2x2 / v4_32 for a 4- / 16-chip TPU
    mesh.  A TPU mesh of any other size has no price list and raises —
    pricing real chips at the CPU loopback's declared rate would be a
    wrong number, not a conservative one."""
    import jax

    from harp_tpu.parallel.mesh import current_mesh

    mesh = mesh or current_mesh()
    n = mesh.num_workers
    if n == 1:
        return single_chip()
    if jax.default_backend() != "tpu":
        return sim_ring(n)
    by_size = {4: v5e_2x2, 16: v4_32}
    if n not in by_size:
        raise ValueError(
            f"plan.topology: no declared topology for a {n}-chip TPU mesh "
            f"(known: {sorted(by_size)}) — add one with its link rates "
            "and their source")
    return by_size[n]()


def probed(topo: Topology, mesh=None, size_mb: float = 4.0) -> Topology:
    """Replace a topology's DECLARED intra-class rate with one measured
    through :func:`harp_tpu.benchmark.bench_verb` (allreduce at
    ``size_mb``) — the probed-rates half of the ISSUE's "probed/declared"
    contract.  Runs wherever the mesh runs (CPU sim included).  The
    inter-host rate keeps its declared value until a multi-host probe
    exists — the stamp says ``probed`` either way so consumers can ask.
    """
    from harp_tpu import benchmark as B
    from harp_tpu.parallel.mesh import current_mesh

    mesh = mesh or current_mesh()
    rec = B.bench_verb("allreduce", mesh, int(size_mb * (1 << 20)), reps=2)
    rate_gbs = rec["gb_per_sec"]
    return dataclasses.replace(topo, intra_gbs=max(rate_gbs, 1e-3),
                               rates_source="probed")
