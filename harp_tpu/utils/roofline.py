"""Roofline annotation — "X% of chip peak", not "faster than yesterday".

Reference parity (SURVEY.md §7 / round-1 VERDICT item 9): BASELINE.md's
numbers need a roofline column so a measured rate reads as a fraction of
what the chip can do.  Each graded config gets an ANALYTIC work model
(FLOPs and minimum HBM bytes per unit of its throughput metric); paired
with a measured benchmark dict it yields achieved TFLOP/s, achieved
GB/s, percent-of-peak for both, and which wall the config is against.

The models are deliberately lower-bound byte models (inputs read once,
outputs written once — XLA fusion can't do better) and exact FLOP
counts for the dominant kernels; percentages can therefore slightly
UNDERSTATE achieved bandwidth but never flatter it.  Peaks are the
published per-chip figures, keyed by the ``device_kind`` JAX reports; a
device that is not in the table is an error, not a default.

PR 13: these work models are also the FLOOR layer of the predictive
cost model (:mod:`harp_tpu.perfmodel.model`), which adds per-variant
mechanism terms on top and self-grades the combined ranking against
the committed bench rows — change a formula here and the perfmodel
grading (tier-1) re-checks every committed ranking it feeds.
"""

from __future__ import annotations

V5E = "TPU v5 lite"  # jax.devices()[0].device_kind on a v5e

# Per-chip peaks by device_kind.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s.  int8 is 2× the bf16 MXU rate (the page rounds it to
    # 393); f32 is the bf16 rate / 4 (HIGHEST precision, 3+ MXU passes).
    V5E: {
        "bf16_flops": 197e12,   # MXU bf16 FLOP/s
        "int8_ops": 394e12,     # MXU int8 OP/s
        "f32_flops": 49.25e12,
        "hbm_gbs": 819e9,       # HBM bandwidth, bytes/s
    },
}
V5E_PEAKS = PEAKS[V5E]  # the perfmodel prices a v5e by name


def peaks_for(device_kind: str) -> dict | None:
    """The peak table of ``device_kind``; None on the CPU (a CPU run has
    no roofline), ValueError for any other kind the table lacks."""
    if device_kind == "cpu":
        return None
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"roofline: no published peaks for device kind "
            f"{device_kind!r} — add it to roofline.PEAKS with its "
            "source") from None


# Matmul-dominated configs with f32 arrays compare against the bf16 peak:
# jax's DEFAULT matmul precision executes f32 dots as single bf16 MXU
# passes (none of the hot kernels request HIGHEST), so the compute wall
# really is 197 TF/s.  Proven on silicon 2026-07-31: kmeans_stream
# measured 131 TF/s ex-gen — impossible against the 49.25 TF/s f32 peak
# the annotator used before this fix (it reported 129% of peak).
_DEFAULT_PRECISION_PEAK = "bf16_flops"


def _kmeans_work(r):
    """Per iteration: distance matmul 2ndk + one-hot sums matmul 2nkd;
    min bytes = points read once (dtype-sized) + assignments written once
    (int32) — the fused kernel never materializes the [n,k] scores in
    HBM, so charging 8nk would INFLATE achieved bandwidth (at k=1000 it
    reported >100% of HBM peak, impossible).  iters_per_sec is a
    WHOLE-MESH rate over the whole-n workload, so the per-chip comparison
    divides by num_workers.  The streaming benchmark reports
    ``iters_per_sec_ex_gen`` (Lloyd time with the synthetic
    chunk-generation scaffolding subtracted) — prefer it when present,
    since generation is benchmark overhead outside this work model."""
    n, d, k = r["n"], r["d"], r["k"]
    dsize = 1 if r.get("quantize") == "int8" else 4
    # value check, not key presence: the streaming benchmark reports
    # ex_gen=None when gen time swamps the epoch (timing noise)
    metric = ("iters_per_sec_ex_gen"
              if r.get("iters_per_sec_ex_gen") is not None
              else "iters_per_sec")
    return {
        "flops": 4.0 * n * d * k,
        "bytes": n * d * dsize + 4.0 * n,
        "per": (metric, 1.0 / r.get("num_workers", 1)),
        "peak": ("int8_ops" if r.get("quantize") == "int8"
                 else _DEFAULT_PRECISION_PEAK),
    }


def _mfsgd_work(r):
    """Per update (one rating): dot(W_u, H_i) + two axpy rows ≈ 6·rank
    FLOPs; min bytes = both rows read + written = 16·rank."""
    rank = r.get("rank", 64)
    return {"flops": 6.0 * rank, "bytes": 16.0 * rank,
            "per": ("updates_per_sec_per_chip", 1.0),
            "peak": _DEFAULT_PRECISION_PEAK}


def _lda_work(r):
    """Per token: K-wide posterior (two logs + gumbel argmax ≈ 10K flops)
    + one-hot delta matmuls ≈ 4K; min bytes = 3 K-rows read + 2 written."""
    K = r["n_topics"]
    return {"flops": 14.0 * K, "bytes": 20.0 * K,
            "per": ("tokens_per_sec_per_chip", 1.0),
            "peak": _DEFAULT_PRECISION_PEAK}


def _mlp_work(r):
    """Per sample: ≈ 6·params FLOPs (fwd 2P + bwd 4P), MNIST-shape MLP
    (784·512 + 512·256 + 256·10 ≈ 535k params); min bytes per sample =
    16·params/batch (params read fwd + bwd, grads written + optimizer
    read-modify-write ≈ 4 param-sized streams of 4 B, amortized over the
    batch).  samples_per_sec is whole-mesh → divide by num_workers."""
    params = 535_818
    return {"flops": 6.0 * params,
            "bytes": 16.0 * params / r.get("batch", 8192),
            "per": ("samples_per_sec", 1.0 / r.get("num_workers", 1)),
            "peak": _DEFAULT_PRECISION_PEAK}


# configs without a trustworthy closed-form model (irregular access
# patterns dominate) are intentionally absent: no number beats a wrong one
WORK_MODELS = {
    "kmeans": _kmeans_work,
    "kmeans_int8": _kmeans_work,
    "kmeans_int8_fused": _kmeans_work,
    # PR 11: the planner's hier-psum candidate only reschedules the
    # collective — compute and HBM floors are the family's
    "kmeans_hier_psum": _kmeans_work,
    "kmeans_stream": _kmeans_work,
    "kmeans_stream_int8": _kmeans_work,
    "mfsgd": _mfsgd_work,
    "mfsgd_scatter": _mfsgd_work,
    "mfsgd_pallas": _mfsgd_work,
    # the carry/approx/hot variants share their family's model.  NB the
    # floor's meaning shifts for carry rows: without carry every entry
    # re-pays its tile, so actual HBM bytes >= the per-update floor and
    # achieved_gbs is a lower bound; WITH carry a run's rows amortize and
    # actual bytes can drop BELOW the floor, so a carry row's
    # achieved_gbs/pct_peak_bw read as the ALGORITHMIC traffic rate (an
    # upper bound on real DRAM), not an achieved-bandwidth claim — the
    # trace pass, not this model, settles real bytes for those rows
    "mfsgd_carry": _mfsgd_work,
    "mfsgd_chunked_rotate": _mfsgd_work,
    "lda": _lda_work,
    "lda_carry": _lda_work,
    "lda_exprace": _lda_work,
    "lda_fast": _lda_work,
    "lda_pallas": _lda_work,
    "lda_pallas_approx": _lda_work,
    "lda_pallas_carry": _lda_work,
    "lda_pallas_hot": _lda_work,
    "lda_pallas_approx_hot": _lda_work,
    "lda_rotate_int8": _lda_work,
    # PR 11: the planner's bf16 wire — same compute, narrower ring only
    "lda_planner_wire": _lda_work,
    "lda_scale": _lda_work,
    "lda_scale_1m": _lda_work,
    "lda_scale_1m_pallas": _lda_work,
    "lda_scatter": _lda_work,
    "mlp": _mlp_work,
}


def annotate(config: str, result: dict, device_kind: str) -> dict:
    """Add roofline fields to a benchmark result dict (returns a copy).

    Adds ``achieved_tflops``, ``achieved_gbs``, ``pct_peak_flops``,
    ``pct_peak_bw`` and ``bound`` ("compute" | "memory" — whichever wall
    is closer) against the peaks of ``device_kind`` (the kind the result
    was measured on).  Adds nothing on ``"cpu"`` and raises on a kind
    :data:`PEAKS` lacks; configs without a work model pass through
    unchanged.
    """
    peaks = peaks_for(device_kind)
    if peaks is None:
        return dict(result)
    model = WORK_MODELS.get(config)
    if model is None:
        return dict(result)
    try:
        w = model(result)
    except KeyError:  # result lacks the shape fields (partial/error record)
        return dict(result)
    metric, scale = w["per"]
    if metric not in result:
        return dict(result)
    rate = float(result[metric]) * scale          # units/s
    flops_s = rate * w["flops"]
    bytes_s = rate * w["bytes"]
    peak_f = peaks[w["peak"]]
    pf = 100.0 * flops_s / peak_f
    pb = 100.0 * bytes_s / peaks["hbm_gbs"]
    out = dict(result)
    out.update({
        "achieved_tflops": round(flops_s / 1e12, 3),
        "achieved_gbs": round(bytes_s / 1e9, 2),
        "pct_peak_flops": round(pf, 2),
        "pct_peak_bw": round(pb, 2),
        "roofline_peak": w["peak"],
        "bound": "compute" if pf >= pb else "memory",
    })
    return out
