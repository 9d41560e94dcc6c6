"""Unified launcher — Harp L8 (``hadoop jar harp-<app>.jar Launcher``) parity.

Harp apps each ship a ``main`` Launcher class invoked through ``hadoop
jar`` with positional args, wrapped by per-app shell scripts (SURVEY.md
§2 L8).  Here every app already has a module-level ``main(argv)``
(``python -m harp_tpu.models.kmeans …``); this dispatcher is the single
front door:

    python -m harp_tpu <app> [app args...]
    python -m harp_tpu bench [--size-mb N]       # collective micro-bench
    python -m harp_tpu --list
"""

from __future__ import annotations

import sys
from importlib import import_module

APPS = {
    "kmeans": ("harp_tpu.models.kmeans", "KMeans Lloyd iterations (allreduce)"),
    "kmeans-stream": ("harp_tpu.models.kmeans_stream",
                      "streaming KMeans for beyond-HBM datasets (1B-point path)"),
    "mfsgd": ("harp_tpu.models.mfsgd", "MF-SGD matrix factorization (rotate)"),
    "ccd": ("harp_tpu.models.ccd", "CCD++ matrix factorization (rotate)"),
    "lda": ("harp_tpu.models.lda", "LDA-CGS topic model (rotate + push/pull)"),
    "mlp": ("harp_tpu.models.mlp", "MLP neural net (gradient allreduce)"),
    "subgraph": ("harp_tpu.models.subgraph", "color-coding subgraph counting"),
    "rf": ("harp_tpu.models.rf", "random forest (allgather of trees)"),
    "svm": ("harp_tpu.models.svm", "distributed linear SVM (allreduce)"),
    "wdamds": ("harp_tpu.models.wdamds", "WDA-MDS / SMACOF embedding"),
    "stats": ("harp_tpu.models.stats",
              "classic analytics: pca/cov/moments/naive/linreg/ridge/qr/svd/als"),
    "serve": ("harp_tpu.serve.server",
              "persistent-mesh inference server (JSONL over stdio)"),
    "bench": ("harp_tpu.benchmark", "collective micro-benchmarks (edu.iu.benchmark)"),
    "report": ("harp_tpu.report",
               "merged run report: comm ledger + spans + metrics + top ops"),
    "trace": ("harp_tpu.utils.reqtrace",
              "request-level timeline: validate/summarize a trace JSONL, "
              "export Chrome/Perfetto trace.json"),
    "timeline": ("harp_tpu.utils.steptrace",
                 "training-plane timeline: validate/summarize kind:'steptrace' "
                 "superstep rows, export Chrome/Perfetto trace.json"),
    "memory": ("harp_tpu.utils.memrec",
               "device-memory ledger: validate/summarize kind:'memory' "
               "buffer-lifecycle rows, re-derive the HBM watermark"),
    "health": ("harp_tpu.health.cli",
               "health sentinel: summarize kind:'health' findings, grade "
               "fresh bench rows, run the fail-closed model gate"),
    "lint": ("harp_tpu.analysis.cli",
             "harplint: static analysis (AST + jaxpr + Mosaic + threads)"),
    "plan": ("harp_tpu.plan.cli",
             "topology-aware collective planner over the lint byte sheets"),
    "predict": ("harp_tpu.perfmodel.cli",
                "offline predictive cost model: price configs/programs, "
                "rank flip candidates, self-grade vs committed evidence"),
    "profile": ("harp_tpu.profile.cli",
                "wall-attribution observatory: capture a driver run, "
                "bucket every op into the mechanism vocabulary, "
                "reconcile against the flightrec/CommLedger spines"),
}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if not argv or argv[0] in ("-h", "--help", "--list"):
        print("usage: python -m harp_tpu <app> [args...]\n\napps:")
        for name, (_, desc) in APPS.items():
            print(f"  {name:10s} {desc}")
        return 0 if argv else 2
    app, rest = argv[0], argv[1:]
    if app not in APPS:
        print(f"unknown app {app!r}; run with --list", file=sys.stderr)
        return 2
    target = APPS[app][0]
    if target.startswith(("harp_tpu.models.", "harp_tpu.serve.",
                          "harp_tpu.benchmark")):
        # the apps that compile for the chip share one persistent cache
        # (the reader/analysis tools never reach a device)
        from harp_tpu.utils import chip

        chip.setup_compile_cache()
    return import_module(target).main(rest) or 0


if __name__ == "__main__":
    sys.exit(main())
