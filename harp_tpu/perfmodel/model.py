"""The offline predictive cost model — price a config without a chip.

Reference parity (SURVEY.md §7): the repo's scarcest resource is chip
time — tile sizes, chunk counts, and wire choices are hand-swept on the
chip (the 2026-08-01 measurement day spent part of its time calibrating
``_tile_rows_int8`` off an OOM).  TACCL (PAPERS.md arXiv:2111.04867) prunes a combinatorial
schedule space with exactly this kind of sketch-plus-profile model.
This module composes the ingredients that already landed:

- **compute/memory** terms from the roofline work models
  (:mod:`harp_tpu.utils.roofline`) extended with per-variant *mechanism
  terms* — each one the measured wall of a committed PROFILE/BENCH row
  (the dense one-hot operand traffic the MF-SGD kernel removes, the
  XLA ``[n, k]`` intermediates the fused kmeans kernel never writes,
  the per-entry tile handoff ``carry_db`` amortizes);
- **wire** terms from the CommGraph byte sheets (PR 9) × the
  :mod:`harp_tpu.plan.topology` link rates (PR 11), with the planner's
  frozen schedule scaling (``predicted_bytes``) for narrow wires;
- **overhead** terms from the per-operation costs the graded rows paid
  (:data:`harp_tpu.utils.flightrec.GRADED_ROW_OVERHEADS` — values of
  2026-07-30, not measured on the current host);
- **kernel shapes** from :mod:`harp_tpu.ops.kernel_registry`'s declared
  work fields and the kernels' own OOM-calibrated VMEM byte models
  (the pre-sizer, :func:`presize`).

**Combination is additive (serial roofline), not max().**  The classic
``max(compute, memory, wire)`` assumes perfect overlap; the committed
evidence refutes that here — ``lda_fast`` (cheaper RNG, same bytes) and
``lda_pallas`` (fewer bytes, same RNG) each measured >1.2× over the
same incumbent, which is impossible if one shared wall dominated both.
The Gibbs/SGD inner phases serialize through VMEM dependencies
(PROFILE_local's op rows are sequential), so the model charges the SUM
of the four terms; ``bound`` names the largest (the diagnosis), and the
per-term breakdown sums to the total exactly — which is what
``scripts/check_jsonl.py`` invariant 12 verifies on every exported
``kind: "model"`` row.

**This is a RANKING model, not a wall-clock predictor** (same contract
as ``plan.topology``): absolute seconds carry declared/floor rates and
are graded only to a loose magnitude band, but the *ordering* of
configs is machine-checked against every committed BENCH_local /
FLIP_DECISIONS / SWEEP_pallas row the model can price
(:mod:`harp_tpu.perfmodel.grade`) — a model that silently drifts from
the evidence fails tier-1, exactly like invariants 1–11.

Calibrated constants each cite their committed evidence inline.  Every
exported row is provenance-stamped and carries ``rates_source``
(declared | probed) so a declared ranking can never masquerade as a
measured one.
"""

from __future__ import annotations

import dataclasses

from harp_tpu.utils.flightrec import GRADED_ROW_OVERHEADS
from harp_tpu.utils.roofline import V5E_PEAKS

#: frozen vocabularies (check_jsonl invariant 12 pins them standalone;
#: tests/test_perfmodel.py asserts the sync)
BOUNDS = ("compute", "memory", "wire", "overhead")
RATES_SOURCES = ("declared", "probed")

# ---------------------------------------------------------------------------
# Chip-class rates (beyond the roofline peaks)
# ---------------------------------------------------------------------------

#: VPU (vector unit) flop rate — DECLARED from the public v5e layout
#: (8×128 lanes × 2 ops × ~1 GHz); the transcendental/PRNG work that
#: never touches the MXU prices against this, not the 197 TF/s matmul
#: peak.
VPU_FLOPS = 2.0e12

#: XLA scatter of small rows — MEASURED 2026-07-30 on v5e (CLAUDE.md:
#: the reason the dense one-hot formulation exists at all).
SCATTER_GBS = 25.0e9

HBM_GBS = float(V5E_PEAKS["hbm_gbs"])

# ---------------------------------------------------------------------------
# Calibrated mechanism constants (each cites its committed evidence)
# ---------------------------------------------------------------------------

#: threefry2x32 cost per 32-bit word on the VPU (~20 rounds × ~3 ops +
#: key schedule).  The binding term behind the measured lda_fast flip:
#: rng_impl="rbg" was +24% where sampler="exprace" alone was ±2%
#: (BENCH_local 2026-08-01) — bit GENERATION, not sampler math, was the
#: wall, so the model must price it.
THREEFRY_FLOPS_PER_WORD = 96.0
#: the hardware RBG path: effectively free next to threefry.
RBG_FLOPS_PER_WORD = 4.0

#: per-topic VPU flops of the two samplers (roofline's 10K gumbel
#: estimate; exprace measured "~5× fewer VPU transcendentals",
#: LDAConfig.sampler's comment).
GUMBEL_VPU_FLOPS_PER_TOPIC = 10.0
EXPRACE_VPU_FLOPS_PER_TOPIC = 2.0

#: HBM round trips of the XLA [n, k] intermediates the dense kmeans
#: formulation materializes per iteration (score write/read, one-hot
#: write, two matmul operand reads) — "the XLA int8 path's wall is the
#: ~2 GB/iter [n, k] intermediates" (at the graded
#: 1M×100 shape 5 × 4nk = 2.0 GB exactly).  The fused Pallas kernels
#: never write them (single HBM pass, ops/kmeans_kernel.py).
KMEANS_XLA_NK_PASSES = 5

#: HBM round trips of the per-token [chunk, K] posteriors the dense XLA
#: LDA path materializes between fusions (scores, noise, one-hot) —
#: the traffic the VMEM-resident kernel absorbs (PROFILE_local
#: 2026-08-01: the kernel row's win is exactly this term).
LDA_XLA_TOKEN_ROUNDTRIPS = 6

#: per-(tile-pair) entry handoff cost for the tiled LDA algos, in HBM
#: byte-equivalents: tile load/flush + kernel program overhead per
#: entry.  CALIBRATED once against the committed SWEEP_pallas d_tile
#: pair (2026-08-01: 8.02M tok/s @512 vs 4.56M @256 — smaller tiles
#: mean quadratically more tile pairs); the self-grading pins the
#: ranking, so drift fails tier-1.
LDA_ENTRY_OVERHEAD_BYTES = float(1 << 20)

#: per-grid-program fixed cost of the MF-SGD Pallas kernel, in HBM
#: byte-equivalents (the grid is (users/tile)·(items/tile) programs —
#: quadratic in 1/tile).  CALIBRATED once against the committed
#: SWEEP_pallas tile sweep (2026-08-01: 250.2M @256 > 195.5M @512 >
#: 163.3M @1024 > 147.3M @128); the self-grading pins the full
#: 4-point ranking.
MFSGD_GRID_OVERHEAD_BYTES = float(24 << 10)

#: host→device staging rate of the machine the graded rows were
#: measured on — the committed probe_h2d row (2026-08-01: 29.9–40.5
#: MB/s across the 16–157 MB probes; the same 30 MB/s
#: flightrec.GRADED_ROW_OVERHEADS["h2d_gbs"] pins).  NOT measured on the
#: current host, whose link is far faster; kept because the committed
#: svm/wdamds/subgraph/rf rows it explains paid it (re-calibration:
#: ROADMAP Design 6).  The PR-16 attribution pass (python -m harp_tpu profile)
#: priced the unpriced half of the codebase by exposing WHERE this
#: term belongs: svm/wdamds/subgraph/rf committed metrics time
#: fit()/count() INCLUDING the per-run shard_array staging, so their
#: models must charge it — while the kmeans/mfsgd/lda epoch metrics
#: stage once outside the timed region and never pay it.
GRADED_ROW_H2D_GBS = float(GRADED_ROW_OVERHEADS["h2d_gbs"])

#: svm pegasos x-shard passes per (outer × inner) step: the margin
#: read and the violator-gradient read (models/svm._pegasos) — storing
#: the shard bf16 (x_dtype knob) halves both.
SVM_X_PASSES_PER_STEP = 2.0

#: wdamds SMACOF [n_loc, N] elementwise passes per iteration (distance
#: write+read, ratio write+read, the two delta reads, sqrt mask) —
#: counted from models/wdamds.make_smacof_fn; the delta reads (2 of
#: the passes) shrink with the staged dtype (delta_dtype knob).
WDAMDS_NN_PASSES = 7.0
#: VPU flops per [n_loc, N] entry (sqrt + div + where + guards).
WDAMDS_VPU_FLOPS_PER_ENTRY = 16.0

#: subgraph overflow-arm constants, CALIBRATED once against the two
#: committed segment-vs-onehot A/B deltas (BENCH_local 2026-08-01): at
#: 100k powerlaw (719,074 overflow entries) onehot won by 0.330
#: s/trial; at graded 1M (3,682,709 entries) segment won by 0.456
#: s/trial.  Solving the two-term model for both deltas gives the
#: per-overflow-entry segment-sum cost and the per-tile onehot program
#: cost; grade.py pins the resulting direction at both scales (the
#: round-5 joint gate refused the flip for exactly this crossover).
SUBGRAPH_SEG_ENTRY_S = 2.162e-6
SUBGRAPH_ONEHOT_TILE_S = 2.244e-3
SUBGRAPH_ROW_TILE = 512.0       # models/subgraph row_tile default
SUBGRAPH_ENTRY_TILE = 2048.0    # onehot tile entry capacity
#: DP traversal gather width per vertex per trial: one [deg] neighbor
#: row per template child, ~20 effective DP columns for graded u5-tree.
SUBGRAPH_DP_COLS = 20.0


#: per-grid-program centroid-operand reload of the fused int8 kmeans
#: kernel: the 5·kp·d term of ``_tile_rows_int8``'s OOM-calibrated
#: byte model (bigger tiles amortize it — the mechanism behind the
#: measured monotone tile sweep 557.9 @8000 > ... > 464.9 @1000).
def _kmeans_reload_bytes(d: int, kp: int) -> float:
    return 5.0 * kp * d


def _lane_pad(k: int) -> int:
    return -(-k // 128) * 128


# ---------------------------------------------------------------------------
# Price: the per-config term sheet
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Price:
    """One config's predicted per-unit time, with the term breakdown."""

    config: str
    metric: str              # the throughput metric 1/predicted_s predicts
    compute_s: float
    memory_s: float
    wire_s: float
    overhead_s: float

    @property
    def predicted_s(self) -> float:
        return (self.compute_s + self.memory_s + self.wire_s
                + self.overhead_s)

    @property
    def predicted_rate(self) -> float:
        return 1.0 / self.predicted_s

    @property
    def bound(self) -> str:
        terms = self.terms()
        return max(BOUNDS, key=lambda b: terms[f"{b}_s"])

    def terms(self) -> dict:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "wire_s": self.wire_s, "overhead_s": self.overhead_s}


def _mk_price(config, metric, *, mxu_flops=0.0, mxu_peak="bf16_flops",
              vpu_flops=0.0, hbm_bytes=0.0, scatter_bytes=0.0,
              wire_s=0.0, units_per_run=1.0, compiles=0.0,
              h2d_bytes=0.0) -> Price:
    compute = mxu_flops / V5E_PEAKS[mxu_peak] + vpu_flops / VPU_FLOPS
    memory = hbm_bytes / HBM_GBS + scatter_bytes / SCATTER_GBS
    # h2d_bytes: per-RUN host→device staging, charged only by
    # families whose committed metric times it (see GRADED_ROW_H2D_GBS)
    ovh = (GRADED_ROW_OVERHEADS["dispatch_s"]
           + GRADED_ROW_OVERHEADS["readback_s"]
           + compiles * GRADED_ROW_OVERHEADS["compile_s"]
           + h2d_bytes / GRADED_ROW_H2D_GBS) / units_per_run
    return Price(config, metric, compute, memory, wire_s, ovh)


def wire_cost_s(topo, primitive: str, schedule: str,
                sheet_bytes: int) -> float:
    """Price one (collective site, schedule) pair on a topology — THE
    shared wire oracle: ``plan.planner._site_cost`` delegates here (the
    Plan rows' cost column and the model's wire term are one function),
    and the config models below reuse it for their analytic payloads.
    The sheet's bytes are already amplification-folded, so the topology
    sees amplification=1."""
    from harp_tpu.plan.planner import predicted_bytes

    if schedule == "hier_psum":
        return topo.hier_stage_cost_s(sheet_bytes)
    return topo.cost_s(primitive, predicted_bytes(schedule, sheet_bytes))


def _wire_schedule(wire: str | None) -> str:
    return {None: "keep", "bf16": "wire_bf16",
            "int8": "wire_int8"}[wire]


# ---------------------------------------------------------------------------
# Family models
# ---------------------------------------------------------------------------

def _price_kmeans(row, topo, *, quantize=None, fused=False, hier=False,
                  tile=None, config, metric="iters_per_sec"):
    """Per Lloyd iteration over the local shard."""
    nw = max(int(row.get("num_workers") or 1), 1)
    n = float(row.get("n", 1_000_000)) / nw
    d = float(row.get("d", 300))
    k = float(row.get("k", 100))
    dsize = 1 if quantize == "int8" else 4
    mxu_peak = "int8_ops" if quantize == "int8" else "bf16_flops"
    hbm = n * d * dsize + 4.0 * n
    if fused:
        kp = _lane_pad(int(k))
        # the measured-best default tile; sweep pricing overrides by row
        tn = float(tile or row.get("tile") or 8000)
        hbm += (n / tn) * _kmeans_reload_bytes(int(d), kp)
    else:
        # the XLA formulation's [n, k] intermediates (see constant)
        hbm += KMEANS_XLA_NK_PASSES * 4.0 * n * k
    psum_bytes = int(4 * (k * d + k + 1))
    wire = (topo.hier_stage_cost_s(psum_bytes) if hier
            else wire_cost_s(topo, "psum", "keep", psum_bytes))
    return _mk_price(config, metric, mxu_flops=4.0 * n * d * k,
                     mxu_peak=mxu_peak, hbm_bytes=hbm, wire_s=wire,
                     units_per_run=float(row.get("iters", 100)))


def _price_mfsgd(row, topo, *, algo="dense", tile=None, wire=None,
                 config, metric="updates_per_sec_per_chip"):
    """Per rating update (one (w_u, h_i) SGD pair)."""
    rank = float(row.get("rank", 64))
    nnz = float(row.get("nnz", 20_000_000))
    n_items = float(row.get("n_items", 26_744))
    n_users = float(row.get("n_users", 138_493))
    ec = float(row.get("entry_cap", 2048))
    nw = max(int(row.get("num_workers") or 1), 1)
    floor = 16.0 * rank                       # both rows read + written
    hbm, scat = floor, 0.0
    if algo == "dense":
        # one-hot operand traffic: the ohu/ohi rows the MXU reads per
        # update (PROFILE_local 2026-08-01: "MF-SGD's wall was one-hot
        # operand traffic (kernel removes it)"); dense auto-tiles 512.
        t = float(tile or row.get("tile") or 512)
        hbm += 4.0 * 2 * t
    elif algo == "pallas":
        # the kernel keeps one-hots in VMEM; what remains is the W/H
        # slice handoff per entry (grows with tile) and the grid-program
        # overhead ((users/t)·(items/t) programs — shrinks with tile²):
        # the U-shape the committed SWEEP_pallas tile sweep measured.
        t = float(tile or row.get("tile") or 256)  # measured-best default
        hbm += 8.0 * rank * t / ec
        hbm += (MFSGD_GRID_OVERHEAD_BYTES
                * (n_users / nw) * (n_items / t / t) / (nnz / nw))
    else:                                     # scatter
        scat = floor                          # rows move at the scatter wall
        hbm = 0.0
    rot_bytes = int(n_items * rank * 4 / nw)  # one H slice per hop
    wire_s = wire_cost_s(topo, "ppermute", _wire_schedule(wire),
                         rot_bytes * nw) / (nnz / nw)
    units = float(row.get("epochs", 3)) * nnz / nw
    return _mk_price(config, metric, mxu_flops=6.0 * rank,
                     vpu_flops=0.0, hbm_bytes=hbm, scatter_bytes=scat,
                     wire_s=wire_s, units_per_run=units)


def _price_lda(row, topo, *, algo="dense", carry=False, sampler="gumbel",
               rng="threefry", wire=None, config,
               metric="tokens_per_sec_per_chip"):
    """Per Gibbs token."""
    K = float(row.get("n_topics", 1000))
    n_tokens = float(row.get("n_tokens", 10_000_000))
    n_docs = float(row.get("n_docs", 100_000))
    vocab = float(row.get("vocab_size", 50_000))
    dt = float(row.get("d_tile", 512))
    wt = float(row.get("w_tile", 512))
    ec = float(row.get("entry_cap", 2048))
    nw = max(int(row.get("num_workers") or 1), 1)
    vpu = (GUMBEL_VPU_FLOPS_PER_TOPIC if sampler == "gumbel"
           else EXPRACE_VPU_FLOPS_PER_TOPIC) * K
    vpu += (THREEFRY_FLOPS_PER_WORD if rng == "threefry"
            else RBG_FLOPS_PER_WORD) * K
    hbm, scat = 12.0, 0.0                     # the token id stream
    if algo == "scatter":
        scat = 8.0 * K                        # two K-rows at the scatter wall
    else:
        # tiled algos: per-entry tile traffic (carry_db removes the
        # doc-tile load+flush inside an od-run — VERDICT r3 item 2) ...
        hbm += 4.0 * K * ((2 * wt) if carry else (2 * dt + 2 * wt)) / ec
        # ... plus the per-(tile-pair) entry handoff, quadratic in
        # 1/tile (see LDA_ENTRY_OVERHEAD_BYTES)
        hbm += (LDA_ENTRY_OVERHEAD_BYTES
                * (n_docs * vocab / nw) / (dt * wt) / (n_tokens / nw))
        if algo == "dense":
            # XLA inter-fusion [chunk, K] materializations the kernel
            # absorbs (see LDA_XLA_TOKEN_ROUNDTRIPS)
            hbm += LDA_XLA_TOKEN_ROUNDTRIPS * 4.0 * K
    rot_bytes = int(vocab * K * 4 / nw)       # one Nwk slice per hop
    wire_s = wire_cost_s(topo, "ppermute", _wire_schedule(wire),
                         rot_bytes * nw) / (n_tokens / nw)
    units = float(row.get("epochs", 2)) * n_tokens / nw
    return _mk_price(config, metric, mxu_flops=4.0 * K, vpu_flops=vpu,
                     hbm_bytes=hbm, scatter_bytes=scat, wire_s=wire_s,
                     units_per_run=units)


def _price_mlp(row, topo, *, wire=None, config, metric="samples_per_sec"):
    """Per training sample (MNIST-shape MLP, roofline's param count)."""
    params = 535_818.0
    batch = float(row.get("batch", 8192))
    steps = float(row.get("steps", 50))
    psum_bytes = int(4 * params)
    wire_s = wire_cost_s(topo, "psum", _wire_schedule(wire),
                         psum_bytes) / batch
    return _mk_price(config, metric, mxu_flops=6.0 * params,
                     hbm_bytes=16.0 * params / batch, wire_s=wire_s,
                     units_per_run=batch * steps)


def _price_rf(row, topo, *, hist="dense", config, metric="trees_per_sec"):
    """Per grown tree (models/rf: level-synchronous growth + forest
    allgather).  The hist knob makes CLAUDE.md's 25 GB/s scatter-wall
    claim (measured 2026-07-30 on 1x v5e) a priced A/B on THIS app:
    the dense arm is one int8 one-hot MXU matmul per level (node count
    doubles per level, so the flop sum telescopes to ``2^depth - 1``
    node-columns) re-reading the [n, f·B] bin-onehot operand each
    level — PLUS the [n, node·C] one-hot operand it materialises in HBM
    between the fusion and the contraction; the scatter arm moves the
    same ``depth·n·f`` histogram updates at SCATTER_GBS instead; the
    pallas arm (PR 17, ops/rf_kernel.py) builds the one-hot in VMEM, so
    only the per-grid-program fixed cost remains of that term."""
    nw = max(int(row.get("num_workers") or 1), 1)
    n = float(row.get("n", 200_000)) / nw
    f = float(row.get("features", 64))
    bins = float(row.get("n_bins", 32))
    classes = float(row.get("n_classes", 2))
    depth = float(row.get("depth", 6))
    n_trees = float(row.get("n_trees", 32))
    nodes = 2.0 ** depth - 1.0
    mxu, hbm, scat = 0.0, 0.0, 0.0
    if hist in ("dense", "pallas"):
        mxu = 2.0 * n * classes * f * bins * nodes
        hbm = depth * n * f * bins
        if hist == "pallas":
            # presize-predicted default tile (2026-08-06, unmeasured)
            tn = float(row.get("tile") or 2048)
            hbm += depth * (n / tn) * MFSGD_GRID_OVERHEAD_BYTES
        else:
            # the [n, node·C] one-hot write + MXU read-back, telescoped
            # over levels — the traffic the kernel keeps in VMEM
            hbm += 2.0 * n * classes * nodes
    else:
        scat = depth * n * f * 4.0
    tree_bytes = (2.0 ** depth) * 4.0 * 4.0   # feat/thresh/route/leaf
    wire = wire_cost_s(topo, "all_gather", "keep",
                       int(n_trees * tree_bytes / nw)) / n_trees
    # fit() stages the binned shard + labels per run; the committed rf
    # row's fit_sec times that staging (see GRADED_ROW_H2D_GBS)
    return _mk_price(config, metric, mxu_flops=mxu, mxu_peak="int8_ops",
                     hbm_bytes=hbm, scatter_bytes=scat, wire_s=wire,
                     units_per_run=n_trees,
                     h2d_bytes=n * nw * (f * 4.0 + 4.0))


def _price_svm(row, topo, *, x_dtype="f32", algo="xla", wire=None, config,
               metric="samples_per_sec"):
    """Per training sample over the full dataset (models/svm: the whole
    multi-round pegasos run is ONE jit; ``fit`` re-stages the x shard
    per call, so the committed samples_per_sec includes the staging —
    at the graded row's staging rate that term dominates, which is why
    the bf16-shard knob is the flip candidate).  The pallas arm (PR 17,
    ops/svm_kernel.py) fuses the two per-step feature passes into one
    plus the sequential grid's per-program cost."""
    nw = max(int(row.get("num_workers") or 1), 1)
    n = float(row.get("n", 500_000))
    d = float(row.get("d", 128))
    steps = (float(row.get("inner_steps", 200))
             * float(row.get("outer_rounds", 5)))
    sv = float(row.get("sv_per_worker", 256))
    xsize = 2.0 if x_dtype == "bf16" else 4.0
    if algo == "pallas":
        # presize-predicted default tile (2026-08-06, unmeasured)
        tn = float(row.get("tile") or 8192)
        hbm = steps * (d * xsize + MFSGD_GRID_OVERHEAD_BYTES / tn) / nw
    else:
        hbm = steps * SVM_X_PASSES_PER_STEP * d * xsize / nw
    sv_bytes = int(sv * d * 4 * nw)           # SV exchange, all shards
    wire_s = (float(row.get("outer_rounds", 5))
              * (wire_cost_s(topo, "ppermute", _wire_schedule(wire),
                             sv_bytes)
                 + wire_cost_s(topo, "psum", "keep", int(d * 4)))) / n
    return _mk_price(config, metric,
                     mxu_flops=steps * 4.0 * d / nw,
                     hbm_bytes=hbm,
                     wire_s=wire_s, units_per_run=n,
                     h2d_bytes=n * (d * xsize + 4.0))


def _price_wdamds(row, topo, *, delta_dtype="f32", algo="xla", wire=None,
                  config, metric="iters_per_sec"):
    """Per SMACOF iteration (models/wdamds: one jit scan over iters;
    ``fit`` stages the [n, n] delta per run — at the graded row's
    staging rate that staging IS the committed wall, so the bf16-delta
    knob that halves it is the flip candidate).  The pallas arm (PR 17,
    ops/wdamds_kernel.py) fuses the D/ratio blocks into VMEM: δ streams
    once, X^T loads once, only the per-grid-program cost remains of the
    WDAMDS_NN_PASSES round-trips."""
    nw = max(int(row.get("num_workers") or 1), 1)
    n = float(row.get("n", 4096))
    dim = float(row.get("dim", 3))
    iters = float(row.get("iters", 30))
    dsize = 2.0 if delta_dtype == "bf16" else 4.0
    n_loc = n / nw
    if algo == "pallas":
        # presize-predicted default tile (2026-08-06, unmeasured)
        tn = float(row.get("tile") or 128)
        hbm = (n_loc * n * dsize            # the one δ stream
               + n * 128.0 * 4.0            # resident X^T load
               + (n_loc / tn) * MFSGD_GRID_OVERHEAD_BYTES)
    else:
        hbm = n_loc * n * ((WDAMDS_NN_PASSES - 2.0) * 4.0 + 2.0 * dsize)
    wire_s = (wire_cost_s(topo, "ppermute", _wire_schedule(wire),
                          int(n * dim * 4))
              + wire_cost_s(topo, "psum", "keep", 4))
    return _mk_price(config, metric,
                     # distance + Guttman-transform matmuls
                     mxu_flops=4.0 * n_loc * n * dim,
                     vpu_flops=WDAMDS_VPU_FLOPS_PER_ENTRY * n_loc * n,
                     hbm_bytes=hbm,
                     wire_s=wire_s, units_per_run=iters,
                     h2d_bytes=n * n * dsize)


def _price_subgraph(row, topo, *, overflow="segment", deg=64.0,
                    ovf_default=0.0, config, metric="vertices_per_sec"):
    """Per vertex per color-coding trial (models/subgraph).  The padded
    [n, deg] CSR (nbr int32 + msk f32) ships per run — the dominant
    committed term — plus the calibrated overflow arm: segment-sum cost
    linear in overflow entries vs the onehot arm's per-tile program
    cost (tiles grow with BOTH n/row_tile windows and entries/tile
    capacity — the crossover the 1M A/B measured)."""
    n = float(row.get("n_vertices", 100_000))
    ovf = float(row.get("overflow_edges", ovf_default))
    base = _mk_price(config, metric,
                     hbm_bytes=deg * 4.0 * SUBGRAPH_DP_COLS,
                     wire_s=wire_cost_s(topo, "psum", "keep", 8) / n,
                     units_per_run=n,
                     h2d_bytes=n * deg * 8.0 + ovf * 12.0)
    if overflow == "onehot":
        tiles = n / SUBGRAPH_ROW_TILE + ovf / SUBGRAPH_ENTRY_TILE
        extra = SUBGRAPH_ONEHOT_TILE_S * tiles / n
    else:
        extra = SUBGRAPH_SEG_ENTRY_S * ovf / n
    return dataclasses.replace(base, memory_s=base.memory_s + extra)


def _price_serve(row, topo, *, app="kmeans", batch_default=64.0,
                 config, metric="qps"):
    """Per served request — the serve-plane queueing term: one
    dispatch+readback per batch window amortized over its rows, plus
    the app's per-row executor work (state reload amortized per
    window).  Batch shapes come from the row's own
    ``n_requests/steady_dispatches`` when present; defaults are
    CALIBRATED from the committed sustained rows (2026-08-04 CPU sim:
    serve_kmeans_sustained 4096 req / 23 dispatches ≈ 178 rows/window
    at 30,183 qps; serve_mfsgd_sustained 4096/15 ≈ 273 at 7,011 qps)
    and the burst rung (burst_admit=64).  CPU rows are excluded from
    magnitude grading — this term RANKS batching configs against the
    graded rows' dispatch cost, it does not reproduce CPU walls."""
    nr, sd = row.get("n_requests"), row.get("steady_dispatches")
    batch = (float(nr) / float(sd)) if nr and sd else float(batch_default)
    rows = float(row.get("rows_per_request", 1))
    if app == "kmeans":
        k, d = float(row.get("k", 100)), float(row.get("d", 300))
        mxu = 2.0 * d * k * rows
        hbm = (d + k) * 4.0 * rows + k * d * 4.0 / batch
    else:                                     # mfsgd top-k scorer
        rank = float(row.get("rank", 64))
        items = float(row.get("n_items", 26_744))
        mxu = 2.0 * rank * items * rows
        hbm = items * 4.0 * rows + items * rank * 4.0 / batch
    return _mk_price(config, metric, mxu_flops=mxu, hbm_bytes=hbm,
                     units_per_run=batch)


# ---------------------------------------------------------------------------
# The config table
# ---------------------------------------------------------------------------

def _k(**kw):
    return ("kmeans", kw)


def _m(**kw):
    return ("mfsgd", kw)


def _l(**kw):
    return ("lda", kw)


def _p(**kw):
    return ("mlp", kw)


def _r(**kw):
    return ("rf", kw)


def _s(**kw):
    return ("svm", kw)


def _w(**kw):
    return ("wdamds", kw)


def _g(**kw):
    return ("subgraph", kw)


def _q(**kw):
    return ("serve", kw)


#: config -> (family, variant kwargs).  PR 16's attribution pass
#: (``python -m harp_tpu profile``) priced the previously-UNPRICEABLE
#: half — rf/svm/wdamds/subgraph and the serve plane now carry
#: mechanism terms — so the only configs still absent are the
#: host-bound ingest twins (kmeans_ingest*: disk generation dominates,
#: no device mechanism to rank): no number beats a wrong one, the same
#: rule as roofline.WORK_MODELS.
CONFIG_MODELS = {
    "kmeans": _k(),
    "kmeans_int8": _k(quantize="int8"),
    "kmeans_int8_fused": _k(quantize="int8", fused=True),
    "kmeans_hier_psum": _k(hier=True),
    "kmeans_stream": _k(metric="iters_per_sec_ex_gen"),
    "kmeans_stream_int8": _k(quantize="int8",
                             metric="iters_per_sec_ex_gen"),
    "mfsgd": _m(),
    "mfsgd_scatter": _m(algo="scatter"),
    "mfsgd_pallas": _m(algo="pallas"),
    "mfsgd_carry": _m(),                      # carry_w: dense ±epsilon
    "mfsgd_chunked_rotate": _m(algo="pallas"),  # chunking re-times hops
    "lda": _l(),
    "lda_carry": _l(carry=True),
    "lda_exprace": _l(sampler="exprace"),
    "lda_fast": _l(sampler="exprace", rng="rbg"),
    "lda_pallas": _l(algo="pallas"),
    "lda_pallas_approx": _l(algo="pallas"),   # gather width: MXU-side only
    "lda_pallas_hot": _l(algo="pallas"),
    "lda_pallas_approx_hot": _l(algo="pallas"),
    "lda_pallas_carry": _l(algo="pallas", carry=True),
    "lda_rotate_int8": _l(algo="pallas", carry=True, wire="int8"),
    "lda_planner_wire": _l(algo="pallas", carry=True, wire="bf16"),
    "lda_scatter": _l(algo="scatter"),
    "lda_scale": _l(),
    "lda_scale_1m": _l(),
    "lda_scale_1m_pallas": _l(algo="pallas", carry=True),
    "mlp": _p(),
    "mlp_grad_bf16": _p(wire="bf16"),
    "mlp_grad_int8": _p(wire="int8"),
    # PR 16: the attribution observatory's newly priced half.
    "rf": _r(),
    "rf_dense_hist": _r(),                    # the hist_algo A/B, dense arm
    "rf_scatter_hist": _r(hist="scatter"),
    # PR 17: the kernelized arms (presize-predicted, unmeasured on a
    # chip)
    "rf_hist_pallas": _r(hist="pallas"),
    "svm": _s(),
    "svm_sv_bf16": _s(wire="bf16"),
    "svm_sv_int8": _s(wire="int8"),
    "svm_x_bf16": _s(x_dtype="bf16"),         # halve the staged shard
    "svm_kernel_pallas": _s(algo="pallas"),   # PR 17: one fused x pass
    "wdamds": _w(),
    "wdamds_coord_bf16": _w(wire="bf16"),
    "wdamds_coord_int8": _w(wire="int8"),
    "wdamds_delta_bf16": _w(delta_dtype="bf16"),
    "wdamds_dist_pallas": _w(algo="pallas"),  # PR 17: fused D/ratio
    "subgraph": _g(deg=64),
    "subgraph_csr32": _g(deg=32),             # halve the padded-CSR ship
    "subgraph_pl": _g(deg=16, ovf_default=719_074),
    "subgraph_onehot": _g(deg=16, ovf_default=719_074,
                          overflow="onehot"),
    "subgraph_1m": _g(deg=16, ovf_default=3_682_709),
    "subgraph_1m_onehot": _g(deg=16, ovf_default=3_682_709,
                             overflow="onehot"),
    "serve_kmeans": _q(app="kmeans"),
    "serve_kmeans_sustained": _q(app="kmeans", batch_default=178.0),
    "serve_mfsgd_topk": _q(app="mfsgd"),
    "serve_mfsgd_sustained": _q(app="mfsgd", batch_default=273.0),
}

#: committed BENCH_local rows whose config name is a CLI metrics tag,
#: not a config of the price list (svm_cli/wdamds_cli landed 2026-08-01
#: via the app CLIs) — the magnitude band grades them through the
#: incumbent's model.  CONFIG_MODELS itself stays ⊆
#: check_jsonl.KNOWN_MODEL_CONFIGS (tests/test_perfmodel.py): a predict
#: row must never name a config the checker refuses.
CLI_ROW_ALIASES = {"svm_cli": "svm", "wdamds_cli": "wdamds"}

_FAMILY_FNS = {"kmeans": _price_kmeans, "mfsgd": _price_mfsgd,
               "lda": _price_lda, "mlp": _price_mlp,
               "rf": _price_rf, "svm": _price_svm,
               "wdamds": _price_wdamds, "subgraph": _price_subgraph,
               "serve": _price_serve}

#: full-shape overrides for configs whose graded shape differs from the
#: family benchmark defaults (the shapes BENCH_local.jsonl's rows ran);
#: everything else prices at the family defaults baked into the
#: ``_price_*`` row.get defaults.
FULL_SHAPES = {
    "kmeans_stream": {"n": 100_000_000, "k": 1000, "iters": 2},
    "kmeans_stream_int8": {"n": 100_000_000, "k": 1000, "iters": 2},
    "lda_pallas_hot": {"n_docs": 20_000, "vocab_size": 256,
                       "n_topics": 32, "n_tokens": 4_000_000,
                       "d_tile": 128, "w_tile": 128},
    "lda_pallas_approx_hot": {"n_docs": 20_000, "vocab_size": 256,
                              "n_topics": 32, "n_tokens": 4_000_000,
                              "d_tile": 128, "w_tile": 128},
    "lda_scale": {"n_docs": 500_000, "n_tokens": 50_000_000,
                  "epochs": 1},
    "lda_scale_1m": {"n_docs": 1_000_000, "n_tokens": 100_000_000,
                     "epochs": 1},
    "lda_scale_1m_pallas": {"n_docs": 1_000_000, "n_tokens": 100_000_000,
                            "epochs": 1},
    "subgraph_1m": {"n_vertices": 1_000_000},
    "subgraph_1m_onehot": {"n_vertices": 1_000_000},
}


def price(config: str, row: dict | None = None, topo=None) -> Price:
    """Price one config: predicted per-unit seconds + term breakdown.

    ``row`` supplies shape fields (a committed BENCH_local row works
    as-is — the grading harness replays them); absent fields fall back
    to the graded full shapes.  Raises ``KeyError`` for unpriceable
    configs — callers that prune must surface that, never swallow it.
    """
    if config not in CONFIG_MODELS:
        raise KeyError(f"{config!r} has no cost model (unpriceable — "
                       "see CONFIG_MODELS)")
    if topo is None:
        from harp_tpu.plan.topology import single_chip

        topo = single_chip()
    family, kw = CONFIG_MODELS[config]
    merged = dict(FULL_SHAPES.get(config) or {})
    merged.update({k: v for k, v in (row or {}).items() if v is not None})
    return _FAMILY_FNS[family](merged, topo, config=config, **kw)


# ---------------------------------------------------------------------------
# kind:"model" rows
# ---------------------------------------------------------------------------

#: byte-sheet program -> the configs that execute it (tests pin every
#: value against check_jsonl.KNOWN_MODEL_CONFIGS — invariant 12 refuses
#: a model row referencing a config outside that list).
PROGRAM_CONFIGS = {
    "kmeans.fit": ("kmeans", "kmeans_int8", "kmeans_int8_fused"),
    "kmeans.fit_hier": ("kmeans_hier_psum",),
    "ingest.accum_chunk": ("kmeans_ingest", "kmeans_ingest_int8"),
    "ingest.finish_epoch": ("kmeans_stream", "kmeans_stream_int8"),
    "mfsgd.epoch": ("mfsgd", "mfsgd_scatter", "mfsgd_pallas",
                    "mfsgd_carry", "mfsgd_chunked_rotate"),
    "lda.epoch": ("lda", "lda_carry", "lda_exprace", "lda_fast",
                  "lda_pallas", "lda_pallas_carry", "lda_rotate_int8",
                  "lda_planner_wire", "lda_scatter"),
    "serve.kmeans_assign": ("serve_kmeans", "serve_kmeans_sustained"),
    "serve.mfsgd_topk": ("serve_mfsgd_topk", "serve_mfsgd_sustained"),
    "svm.train": ("svm", "svm_sv_bf16", "svm_sv_int8", "svm_x_bf16"),
    "svm.train_pallas": ("svm_kernel_pallas",),
    "wdamds.smacof": ("wdamds", "wdamds_coord_bf16",
                      "wdamds_coord_int8", "wdamds_delta_bf16"),
    "wdamds.smacof_pallas": ("wdamds_dist_pallas",),
    "rf.grow": ("rf", "rf_dense_hist", "rf_scatter_hist"),
    "rf.grow_pallas": ("rf_hist_pallas",),
    "subgraph.count": ("subgraph", "subgraph_csr32", "subgraph_pl",
                       "subgraph_onehot", "subgraph_1m",
                       "subgraph_1m_onehot"),
    "collective.reshard": (), "collective.reshard_wire": (),
    "elastic.regather": (),
    "ring_attention": (), "rotate.pipeline_chunked": (),
    "serve.lda_infer": (), "serve.mlp_logits": (),
    "serve.rf_vote": (), "serve.svm_scores": (),
}


def price_sheet(program: str, sheet: dict, topo) -> Price:
    """Price one program's byte sheet: the wire term summed over every
    collective site (amplification-folded, "keep" schedule — fail
    closed like the planner) plus the per-dispatch overheads.  Compute
    and memory are 0 here: a byte sheet knows wires, not FLOPs — the
    config models above carry those."""
    wire = 0.0
    for e in sheet.get("collectives") or []:
        amped = int(e["per_shard_bytes"]) * max(
            int(e.get("amplification") or 1), 1)
        wire += wire_cost_s(topo, e["primitive"], "keep", amped)
    ovh = (GRADED_ROW_OVERHEADS["dispatch_s"]
           + GRADED_ROW_OVERHEADS["readback_s"])
    return Price(program, "program_runs_per_sec", 0.0, 0.0, wire, ovh)


def model_row(p: Price, topo, *, program: str | None = None,
              config: str | None = None) -> dict:
    """One serializable ``kind: "model"`` record (invariant 12 shape;
    the caller stamps provenance via metrics.benchmark_json)."""
    terms = {k: round(v, 12) for k, v in p.terms().items()}
    return {
        "kind": "model",
        "program": program,
        "config": config,
        "configs": sorted(PROGRAM_CONFIGS.get(program, ()))
        if program else ([config] if config else []),
        "topology": topo.name,
        "rates_source": topo.rates_source,
        "metric": p.metric,
        "predicted_s": round(sum(terms.values()), 12),
        "predicted_rate": round(p.predicted_rate, 4),
        "bound": max(BOUNDS, key=lambda b: terms[f"{b}_s"]),
        "terms": terms,
    }


# ---------------------------------------------------------------------------
# Candidate ranking (``predict --top``)
# ---------------------------------------------------------------------------

def rank_candidates(pairs: dict, topo, rows: dict | None = None) -> dict:
    """Predicted speedup per flip candidate: ``pairs`` maps candidate →
    incumbent config (``grade.FAMILY_PAIRS``); returns
    {candidate: speedup} for every pair the model can price, pricing
    both sides at the SAME shape (the incumbent's committed row when
    ``rows`` has one, else the graded full shape).  Unpriceable
    candidates are simply absent — the caller must report them, not
    guess."""
    out = {}
    for cand, inc in pairs.items():
        if cand not in CONFIG_MODELS or inc not in CONFIG_MODELS:
            continue
        shape = (rows or {}).get(inc)
        t_inc = price(inc, shape, topo).predicted_s
        t_cand = price(cand, shape, topo).predicted_s
        out[cand] = round(t_inc / t_cand, 4)
    return out


# ---------------------------------------------------------------------------
# VMEM pre-sizer
# ---------------------------------------------------------------------------

def presize(kernel: str, **shape) -> dict:
    """Pick a new-silicon-safe tile for a registered Pallas kernel —
    the thing the 2026-08-01 window calibrated by hand off an OOM.

    Consults the kernel's OWN VMEM byte model (one source of truth:
    ``kmeans_kernel._tile_rows_int8``'s OOM-calibrated algebra, the
    mfsgd kernel's resident-H budget) for which tiles FIT, then ranks
    the fitting tiles with the cost model and returns the predicted
    fastest.  Pinned against the measured evidence: 8000 rows for the
    int8 kmeans kernel at the graded shape, 256×256 for MF-SGD
    (tests/test_perfmodel.py).
    """
    if kernel == "kmeans.partials_int8":
        from harp_tpu.ops.kmeans_kernel import _tile_rows_int8

        n, d, k = shape["n"], shape["d"], shape["k"]
        kp = _lane_pad(k)
        tn = _tile_rows_int8(n, d, kp)
        if tn is None:
            return {"kernel": kernel, "tile": None,
                    "reason": "no sublane-aligned tile fits the "
                              "calibrated VMEM budget"}
        # the fused model is monotone in tile (reload amortization), so
        # the largest fitting tile is also the predicted fastest
        return {"kernel": kernel, "tile": tn, "vmem_model":
                "kmeans_kernel._tile_rows_int8 (OOM-calibrated "
                "2026-08-01)"}
    if kernel == "mfsgd.sgd_tile_update":
        rank = shape.get("rank", 64)
        # the kernel holds ONE rotation half-slice of H resident (the
        # chunked rotator hands it 1/(nw * rotate_chunks) of the items)
        ib = shape.get("i_shard") or (
            shape.get("n_items", 26_744)
            // (shape.get("num_workers", 1)
                * shape.get("rotate_chunks", 2)))
        if 2 * ib * rank * 4 > 10 << 20:
            return {"kernel": kernel, "tile": None,
                    "reason": "resident H half-slice exceeds the 10 MB "
                              "VMEM budget; shard over more workers"}
        fits = [t for t in (1024, 512, 256, 128)
                if t % 128 == 0 and 4 * rank * t * 4 + 2 * ib * rank * 4
                <= 14 << 20]
        best = min(fits, key=lambda t: price(
            "mfsgd_pallas", {"tile": t, **shape}).predicted_s)
        return {"kernel": kernel, "tile": best,
                "fits": fits, "vmem_model":
                "mfsgd_kernel resident-H + scratch budget"}
    if kernel == "svm.kernel_row":
        from harp_tpu.ops import svm_kernel

        d = shape["d"]
        xsize = 2 if shape.get("x_dtype") == "bf16" else 4
        fits = svm_kernel.fit_tiles(d, xsize)
        if not fits:
            return {"kernel": kernel, "tile": None,
                    "reason": "no lane-aligned sample tile fits the "
                              "VMEM budget; use algo='xla'"}
        row = {"tile": None, "n": shape.get("n"), "d": d,
               "num_workers": shape.get("num_workers")}
        best = min(fits, key=lambda t: price(
            "svm_kernel_pallas", {**row, "tile": t}).predicted_s)
        return {"kernel": kernel, "tile": best, "fits": fits,
                "vmem_model": "svm_kernel.vmem_bytes (analytic, "
                              "2026-08-06 — unmeasured)"}
    if kernel == "wdamds.smacof_dist":
        from harp_tpu.ops import wdamds_kernel

        n = shape["n"]
        dsize = 2 if shape.get("delta_dtype") == "bf16" else 4
        fits = wdamds_kernel.fit_tiles(n, dsize)
        if not fits:
            return {"kernel": kernel, "tile": None,
                    "reason": "no row tile fits the [tn, N] working set "
                              "under the VMEM budget; use algo='xla' or "
                              "shard over more workers"}
        row = {"tile": None, "n": n, "dim": shape.get("dim"),
               "num_workers": shape.get("num_workers")}
        best = min(fits, key=lambda t: price(
            "wdamds_dist_pallas", {**row, "tile": t}).predicted_s)
        return {"kernel": kernel, "tile": best, "fits": fits,
                "vmem_model": "wdamds_kernel.vmem_bytes (analytic, "
                              "2026-08-06 — unmeasured)"}
    if kernel == "rf.hist_bins":
        from harp_tpu.ops import rf_kernel

        f, bins = shape["f"], shape["n_bins"]
        classes = int(shape.get("n_classes", 2))
        depth = int(shape.get("depth", 6))
        fB = f * bins
        # the deepest grown level holds the most node-classes resident:
        # 2^(depth-1) nodes × C labels, sublane-padded
        nodeCp = 8 * -(-(2 ** (depth - 1) * classes) // 8)
        fits = rf_kernel.fit_tiles(fB, nodeCp)
        if not fits:
            return {"kernel": kernel, "tile": None,
                    "reason": "no sample tile fits fB plus the deepest "
                              "level's histogram under the VMEM budget; "
                              "use hist_algo='dense'"}
        row = {"tile": None, "n": shape.get("n"), "features": f,
               "n_bins": bins, "n_classes": classes, "depth": depth,
               "num_workers": shape.get("num_workers")}
        best = min(fits, key=lambda t: price(
            "rf_hist_pallas", {**row, "tile": t}).predicted_s)
        return {"kernel": kernel, "tile": best, "fits": fits,
                "vmem_model": "rf_kernel.vmem_bytes (analytic, "
                              "2026-08-06 — unmeasured)"}
    raise KeyError(f"no pre-size model for kernel {kernel!r} — register "
                   "one here when the kernel lands (see module doc)")
