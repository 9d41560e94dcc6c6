"""Roofline annotation math (utils/roofline.py)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

import check_jsonl  # noqa: E402

from harp_tpu.utils import roofline as R  # noqa: E402
from harp_tpu.utils.roofline import V5E  # noqa: E402


def test_kmeans_annotation_math():
    # 1M×300 k=100 at 400 iter/s: flops = 4ndk·rate
    r = R.annotate("kmeans", {"n": 1_000_000, "d": 300, "k": 100,
                              "iters_per_sec": 400.0, "quantize": None},
                   V5E)
    want_tflops = 4 * 1e6 * 300 * 100 * 400 / 1e12
    np.testing.assert_allclose(r["achieved_tflops"], round(want_tflops, 3))
    assert 0 < r["pct_peak_flops"] < 100
    # default-precision f32 matmuls run as single bf16 MXU passes, so the
    # compute wall is the bf16 peak (proven on silicon: kmeans_stream
    # measured 131 TF/s > the 49.25 TF/s f32 peak, 2026-07-31)
    assert r["roofline_peak"] == "bf16_flops"
    assert r["bound"] in ("compute", "memory")


def test_int8_uses_int8_peak_and_smaller_bytes():
    base = {"n": 1_000_000, "d": 300, "k": 100, "iters_per_sec": 400.0}
    f32 = R.annotate("kmeans", {**base, "quantize": None}, V5E)
    i8 = R.annotate("kmeans_int8", {**base, "quantize": "int8"}, V5E)
    assert i8["roofline_peak"] == "int8_ops"
    assert i8["pct_peak_flops"] < f32["pct_peak_flops"]  # higher peak
    assert i8["achieved_gbs"] < f32["achieved_gbs"]      # 1-byte points


def test_mesh_aggregate_metrics_divided_per_chip():
    # whole-mesh rates (kmeans iters/s, mlp samples/s) must be divided by
    # num_workers before the single-chip peak comparison — an 8-chip run
    # must not report 8x the per-chip utilization
    base = {"n": 1_000_000, "d": 300, "k": 100, "iters_per_sec": 400.0,
            "quantize": None}
    one = R.annotate("kmeans", {**base, "num_workers": 1}, V5E)
    eight = R.annotate("kmeans", {**base, "num_workers": 8}, V5E)
    np.testing.assert_allclose(eight["pct_peak_flops"] * 8,
                               one["pct_peak_flops"], rtol=1e-2)  # 2-dp rounding


def test_unmodeled_config_passes_through():
    r = {"trees_per_sec": 7.0}
    assert R.annotate("rf", r, V5E) == r
    assert R.annotate("rf", r, V5E) is not r  # copy, not alias


def test_missing_metric_passes_through():
    assert "pct_peak_flops" not in R.annotate("kmeans", {"n": 1}, V5E)


def test_memory_vs_compute_bound_classification():
    # flops:bytes = 4ndk/(4nd+4n) = dk/(d+1) ≈ k for large d.  Machine
    # balance at the bf16 peak is 197 TF / 819 GB/s ≈ 240 flop/byte, so
    # tiny d·k (ratio 1.6) is memory-bound and the graded k=1000 shape
    # (ratio ≈ 997) is compute-bound.
    lo_k = R.annotate("kmeans", {"n": 1 << 20, "d": 4, "k": 2,
                                 "iters_per_sec": 100.0, "quantize": None},
                      V5E)
    hi_k = R.annotate("kmeans", {"n": 1 << 20, "d": 300, "k": 1000,
                                 "iters_per_sec": 100.0, "quantize": None},
                      V5E)
    assert lo_k["bound"] == "memory"
    assert hi_k["bound"] == "compute"


def test_cpu_run_gets_no_roofline_fields():
    # a number from a CPU run is never a share of a device's peak
    r = {"n": 1_000_000, "d": 300, "k": 100, "iters_per_sec": 400.0,
         "quantize": None}
    assert R.annotate("kmeans", r, "cpu") == r
    assert R.peaks_for("cpu") is None


def test_unknown_device_kind_is_an_error_not_a_default():
    r = {"n": 1_000_000, "d": 300, "k": 100, "iters_per_sec": 400.0,
         "quantize": None}
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        R.annotate("kmeans", r, "TPU v9 imaginary")
    # even for a config with no work model: the device is checked first
    with pytest.raises(ValueError, match="no published peaks"):
        R.annotate("rf", {"trees_per_sec": 7.0}, "TPU v9 imaginary")
    assert R.peaks_for(V5E)["hbm_gbs"] == 819e9


def test_variant_configs_share_their_family_model():
    """EVERY mfsgd/lda config name a row may carry must be annotated
    with its family's minimum-byte floor — a variant missing from
    WORK_MODELS yields a row with no roofline fields.  Derived from the
    checker's frozen list of config names so the NEXT variant is guarded
    too."""
    for cfg in check_jsonl.KNOWN_MODEL_CONFIGS:
        for fam in ("mfsgd", "lda"):
            if cfg == fam or cfg.startswith(fam + "_"):
                assert R.WORK_MODELS.get(cfg) is R.WORK_MODELS[fam], cfg
