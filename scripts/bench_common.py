"""Shared benchmark-shape presets for the measurement scripts.

THE smoke shapes, in one place: `measure_all.py`, `profile_configs.py`
and `sweep_pallas.py` all shrink the graded configs to these for fast
CPU-safe passes — a shape change must hit all three identically or the
scripts silently measure different programs (review finding, round 3).
Full graded shapes stay in measure_all (they are the specification of
the sweep, not a tuning knob).
"""

#: per-model smoke kwargs (CPU-safe, seconds per config)
SMOKE = {
    "kmeans": {"n": 8192, "d": 32, "k": 16, "iters": 10},
    "kmeans_stream": {"n": 65536, "d": 16, "k": 16, "iters": 2,
                      "chunk_points": 8192},
    "mfsgd": {"n_users": 512, "n_items": 256, "nnz": 20_000, "rank": 8,
              "epochs": 2, "u_tile": 16, "i_tile": 16, "entry_cap": 256},
    # the pallas kernels gate 128-multiple tiles on TPU
    "mfsgd_pallas": {"n_users": 512, "n_items": 256, "nnz": 20_000,
                     "rank": 8, "epochs": 2, "u_tile": 128, "i_tile": 128,
                     "entry_cap": 256},
    "mfsgd_scatter": {"n_users": 512, "n_items": 256, "nnz": 20_000,
                      "rank": 8, "epochs": 2, "chunk": 1024},
    "lda": {"n_docs": 256, "vocab_size": 128, "n_topics": 8,
            "tokens_per_doc": 16, "epochs": 1, "d_tile": 16, "w_tile": 16,
            "entry_cap": 64},
    "lda_pallas": {"n_docs": 256, "vocab_size": 128, "n_topics": 8,
                   "tokens_per_doc": 16, "epochs": 1, "d_tile": 128,
                   "w_tile": 128, "entry_cap": 64},
    "lda_scatter": {"n_docs": 256, "vocab_size": 128, "n_topics": 8,
                    "tokens_per_doc": 16, "epochs": 1, "chunk": 256},
    "mlp": {"n": 4096, "batch": 512, "steps": 5},
    # serving (PR 6): tiny ladder + state, seconds on the CPU sim; the
    # state_shape kwargs feed the engines' synthetic_state
    "serve_kmeans": {"n_requests": 48, "rows_per_request": 2,
                     "burst": 16, "ladder": (1, 8, 32),
                     "state_shape": {"k": 16, "d": 32}},
    "serve_mfsgd_topk": {"n_requests": 48, "rows_per_request": 2,
                         "burst": 16, "ladder": (1, 8, 32),
                         "state_shape": {"n_users": 256, "n_items": 128,
                                         "rank": 8}},
    # sustained continuous-batching A/B (PR 7): n_requests must exceed
    # the max rung or the backlog can never fill a max-rung batch and
    # the A/B reads ~1.0x at any truth (measured: 256 requests on the
    # 512 ladder gave 0.96x; 2048 gave 1.78x) — the smoke ladder tops
    # at 32 so 96 requests keep the same property in seconds
    "serve_kmeans_sustained": {"n_requests": 96, "rows_per_request": 1,
                               "burst_admit": 8, "ladder": (1, 8, 32),
                               "state_shape": {"k": 16, "d": 32}},
    "serve_mfsgd_sustained": {"n_requests": 96, "rows_per_request": 1,
                              "burst_admit": 8, "ladder": (1, 8, 32),
                              "state_shape": {"n_users": 256,
                                              "n_items": 128,
                                              "rank": 8}},
    "subgraph": {"n_vertices": 2000, "avg_degree": 4},
    "rf": {"n": 4096, "f": 16, "max_depth": 3, "n_trees": 2},
    # PR 12: first svm/wdamds sweep rows (incumbents of the new wire
    # candidates) — small enough for seconds on the CPU sim
    "svm": {"n": 4096, "d": 32},
    "wdamds": {"n": 256},
}

# PR 11 planner candidates measure the SAME shapes as their incumbents
# (only the collective schedule differs — an A/B over different shapes
# would attribute shape noise to the schedule): aliases, not copies, so
# an incumbent smoke-shape change can never drift the pair apart.
SMOKE["kmeans_hier_psum"] = SMOKE["kmeans"]
SMOKE["lda_planner_wire"] = SMOKE["lda_pallas"]
# PR 12 wire candidates measure their incumbents' shapes (only the
# exchange wire differs) — aliases so the pairs can never drift apart
SMOKE["svm_sv_bf16"] = SMOKE["svm_sv_int8"] = SMOKE["svm"]
SMOKE["wdamds_coord_bf16"] = SMOKE["wdamds_coord_int8"] = SMOKE["wdamds"]
# PR 16 profile-priced candidates measure their incumbents' shapes (only
# a dtype / histogram formulation / CSR width differs) — aliases again
SMOKE["rf_dense_hist"] = SMOKE["rf_scatter_hist"] = SMOKE["rf"]
SMOKE["svm_x_bf16"] = SMOKE["svm"]
SMOKE["wdamds_delta_bf16"] = SMOKE["wdamds"]
SMOKE["subgraph_csr32"] = SMOKE["subgraph"]
# PR 17 kernelized arms measure their incumbents' shapes (only the
# kernel schedule differs) — aliases again.  The shared shapes keep the
# pallas branches ENGAGED in smoke mode: svm pads d to 128 lanes
# regardless; wdamds n=256 pads to a 128-multiple; rf f=16 × 32 bins
# gives fB = 512 (odd widths would silently fall back to the XLA arms).
SMOKE["svm_kernel_pallas"] = SMOKE["svm"]
SMOKE["wdamds_dist_pallas"] = SMOKE["wdamds"]
SMOKE["rf_hist_pallas"] = SMOKE["rf"]
