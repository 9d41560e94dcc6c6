"""``python -m harp_tpu lint`` — the harplint front door.

Runs the five analysis layers (AST lints / jaxpr detectors / Mosaic
kernel audit / CommGraph communication audit / thread-root concurrency
audit), applies the committed
allowlist, prints a human report plus ONE provenance-stamped machine
line (``kind: "lint"``, printed through
:func:`harp_tpu.utils.metrics.benchmark_json` so it carries the same
backend/date/commit stamp as every bench row — ``scripts/check_jsonl.py``
invariant 6 validates the shape, including the per-program byte sheets
the CommGraph layer ships in the row), and exits non-zero when any
unallowlisted violation remains OR the allowlist carries a stale entry
(an exception excusing nothing is a rotten review record — prune it).

Fixture mode for tests / pre-commit checks of a single file:

- positional ``paths`` restrict the AST layer to those files;
- ``--changed`` restricts the AST layer to files changed vs git HEAD
  (plus untracked) — the fast dev loop; the traced layers still run in
  full, because they are program-keyed, not file-keyed;
- ``--audit-module FILE`` imports a Python file and sweeps its
  ``HARPLINT_DRIVERS`` (jaxpr + commgraph layers) / ``HARPLINT_KERNELS``
  (Mosaic layer) / ``HARPLINT_PROTOCOLS`` (donation audit) /
  ``HARPLINT_PLANES`` (thread-root layer: name -> (PlaneSpec, sources))
  dicts — the hook the seeded-fixture tests drive the traced layers
  through.

``paths`` / ``--audit-module`` skip the repo-wide default sweeps, so the
exit code reflects only the requested targets (``--changed`` does NOT:
it is a scoped full run, and only staleness reporting is disabled since
an unswept file cannot prove an entry stale).

The jax-touching layers select the CPU backend (8 simulated workers)
before first backend use: a linter only traces, and it must be runnable
beside a process that holds the chip without taking the chip from it.
"""

from __future__ import annotations

import argparse
import os
import sys
from collections import Counter

from harp_tpu.analysis import RULES, Violation, rule_ids
from harp_tpu.analysis import allowlist as allowlist_mod
from harp_tpu.analysis.astlints import iter_python_files, lint_paths
from harp_tpu.analysis.jaxpr_checks import (DEFAULT_CONST_BYTES,
                                            analyze_program)


def repo_root() -> str:
    import harp_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(
        harp_tpu.__file__)))


def _force_cpu_backend() -> None:
    """CPU, 8 simulated workers — BEFORE first backend use (no effect
    when a harness like tests/conftest.py already initialized the
    backend).  The one in-code backend choice in the repository (the
    plan, predict, health and profile CLIs call it too): trace-only
    tools must not take the chip from the process using it."""
    import jax

    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=8")
    jax.config.update("jax_platforms", "cpu")


def _load_audit_module(path: str):
    import importlib.util

    name = f"_harplint_fixture_{os.path.basename(path).removesuffix('.py')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_jaxpr_layer(builders: dict, threshold: int) -> list[Violation]:
    out: list[Violation] = []
    for name in sorted(builders):
        target = f"driver:{name}"
        try:
            fn, args = builders[name]()
        except Exception as e:  # noqa: BLE001 - a broken builder is loud
            out.append(Violation("HL101", target, 0,
                                 f"driver builder failed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        out.extend(analyze_program(fn, args, target, threshold))
    return out


def run_commgraph_layer(builders: dict) -> tuple[list[Violation], dict]:
    """Layer 4 over driver programs: extract each CommGraph, run the
    HL301/HL302/HL304 checks, and return the per-program byte sheets
    (the lint row ships them — the future planner input)."""
    from harp_tpu.analysis import commgraph

    out: list[Violation] = []
    sheets: dict[str, dict] = {}
    for name in sorted(builders):
        target = f"driver:{name}"
        try:
            fn, args = builders[name]()
        except Exception as e:  # noqa: BLE001 - a broken builder is loud
            out.append(Violation("HL301", target, 0,
                                 f"driver builder failed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        try:
            violations, graph = commgraph.analyze_program(name, fn, args)
        except Exception as e:  # noqa: BLE001
            out.append(Violation("HL301", target, 0,
                                 f"commgraph extraction failed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        out.extend(violations)
        sheets[name] = graph.sheet()
    return out, sheets


def run_protocol_layer(builders: dict) -> list[Violation]:
    """Layer 4's donation audit (HL303) over registered host protocols
    — the serve ContinuousRunner depth-2 pipelines at lint time."""
    from harp_tpu.analysis import commgraph

    out: list[Violation] = []
    for name in sorted(builders):
        try:
            drive = builders[name]()
        except Exception as e:  # noqa: BLE001
            out.append(Violation("HL303", f"protocol:{name}", 0,
                                 f"protocol builder failed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        out.extend(commgraph.audit_protocol(name, drive))
    return out


def run_threads_layer(builders: dict | None, repo: str,
                      only: list[str] | None = None) -> list[Violation]:
    """Layer 5 (HL401-HL405) — pure ast, no jax import.  ``builders``
    maps fixture names to ``(PlaneSpec, {relpath: source})`` pairs (the
    ``HARPLINT_PLANES`` hook); ``None`` sweeps the repo's registered
    planes, restricted to ``only`` on ``--changed`` runs."""
    from harp_tpu.analysis import threadgraph

    if builders is None:
        return threadgraph.analyze_repo(repo, only=only)
    out: list[Violation] = []
    for name in sorted(builders):
        try:
            spec, sources = builders[name]
        except Exception as e:  # noqa: BLE001
            out.append(Violation("HL401", f"plane:{name}", 0,
                                 f"plane fixture malformed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        out.extend(threadgraph.analyze_sources(spec, sources))
    return out


def run_mosaic_layer(builders: dict | None) -> list[Violation]:
    from harp_tpu.analysis.mosaic_audit import audit_kernel, audit_registry

    if builders is None:
        return audit_registry()
    out: list[Violation] = []
    for name in sorted(builders):
        try:
            fn, args = builders[name]()
        except Exception as e:  # noqa: BLE001
            out.append(Violation("HL201", f"kernel:{name}", 0,
                                 f"kernel builder failed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        out.extend(audit_kernel(name, fn, args))
    return out


def render(kept: list[Violation], suppressed: list[Violation],
           stale: list[dict], scanned: int) -> str:
    lines = ["== harplint report =="]
    by_rule: dict[str, list[Violation]] = {}
    for v in kept:
        by_rule.setdefault(v.rule, []).append(v)
    for rid in sorted(by_rule):
        rule = RULES.get(rid)
        title = rule.title if rule else "(unregistered rule)"
        lines.append(f"{rid} {title} — {len(by_rule[rid])} violation(s)")
        for v in by_rule[rid]:
            lines.append("  " + v.format().replace("\n", "\n  "))
    lines.append(f"{scanned} file(s) scanned; {len(kept)} violation(s), "
                 f"{len(suppressed)} allowlisted")
    for e in stale:
        lines.append(f"STALE allowlist entry: {e['rule']} {e['path']} "
                     f"({e['reason']}) matched nothing — remove it "
                     "(stale entries fail the lint)")
    lines.append("harplint: " + ("FAILED" if kept or stale else "clean"))
    return "\n".join(lines)


def build_row(kept, suppressed, stale, scanned,
              byte_sheets: dict | None = None) -> dict:
    per_rule = Counter(v.rule for v in kept)
    per_file = Counter(v.path for v in kept)
    row = {
        "kind": "lint",
        "rules": rule_ids(),
        "files_scanned": scanned,
        "violations": len(kept),
        "allowlisted": len(suppressed),
        "stale_allowlist": len(stale),
        "per_rule": dict(sorted(per_rule.items())),
        "per_file": dict(sorted(per_file.items())),
        "clean": not kept,
    }
    if byte_sheets is not None:
        # per-program static comm sheets (full-registry runs only: the
        # program names must come from analysis/drivers.py — check_jsonl
        # invariant 6 pins that, so fixture rows omit the block)
        row["byte_sheets"] = byte_sheets
    return row


def _changed_paths(repo: str) -> list[str]:
    """Repo-relative .py files changed vs git HEAD, plus untracked —
    the ``--changed`` AST scope.  Intersected with the default sweep
    set so deleted/ignored files never error."""
    import subprocess

    changed: set[str] = set()
    for cmd in (["git", "diff", "--name-only", "HEAD"],
                ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            res = subprocess.run(cmd, cwd=repo, capture_output=True,
                                 text=True, timeout=30)
        except Exception:  # pragma: no cover - no git in env
            return []
        if res.returncode != 0:  # pragma: no cover - not a git checkout
            return []
        changed.update(ln.strip() for ln in res.stdout.splitlines()
                       if ln.strip())
    swept = set(iter_python_files(repo))
    return sorted(p.replace(os.sep, "/") for p in changed
                  if p.replace(os.sep, "/") in swept)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m harp_tpu lint",
        description="static analysis (AST lints + jaxpr detectors + "
                    "Mosaic kernel audit + thread ownership)")
    p.add_argument("paths", nargs="*",
                   help="restrict the AST layer to these files "
                        "(repo-relative or absolute); skips the default "
                        "repo-wide sweeps")
    p.add_argument("--changed", action="store_true",
                   help="restrict the AST layer to changed files and the "
                        "thread-root layer to planes owning them (vs git "
                        "HEAD, plus untracked) — the ~2 s dev loop as "
                        "the repo grows; the traced layers still run in "
                        "full (program-keyed, not file-keyed)")
    p.add_argument("--layer",
                   choices=("ast", "jaxpr", "mosaic", "commgraph",
                            "threads", "all"),
                   default="all")
    p.add_argument("--json", action="store_true",
                   help="print only the machine-readable line")
    p.add_argument("--audit-module", action="append", default=[],
                   metavar="FILE",
                   help="sweep FILE's HARPLINT_DRIVERS / HARPLINT_KERNELS "
                        "instead of the repo registries (fixture mode)")
    p.add_argument("--allowlist", default=None,
                   help="allowlist TOML (default: analysis/allowlist.toml)")
    p.add_argument("--no-allowlist", action="store_true")
    p.add_argument("--const-threshold-mb", type=float, default=None,
                   help="HL102 closed-over-constant threshold (default "
                        f"{DEFAULT_CONST_BYTES >> 20} MiB)")
    args = p.parse_args(argv)
    if args.changed and args.paths:
        p.error("--changed and explicit paths are mutually exclusive")

    repo = repo_root()
    # unconditional: even an AST-only run prints a provenance-stamped
    # line (jax.default_backend()), which must never touch the chip
    _force_cpu_backend()
    fixture_mode = bool(args.paths or args.audit_module)
    threshold = (int(args.const_threshold_mb * (1 << 20))
                 if args.const_threshold_mb is not None
                 else DEFAULT_CONST_BYTES)

    violations: list[Violation] = []
    scanned = 0
    changed_rels = (_changed_paths(repo)
                    if args.changed and not fixture_mode else None)

    if args.layer in ("ast", "all"):
        if args.paths:
            rels = [os.path.relpath(os.path.abspath(x), repo)
                    .replace(os.sep, "/") for x in args.paths]
            violations += lint_paths(repo, rels)
            scanned += len(rels)
        elif not fixture_mode:
            rels = (changed_rels if changed_rels is not None
                    else list(iter_python_files(repo)))
            violations += lint_paths(repo, rels)
            scanned += len(rels)

    fixture_drivers: dict = {}
    fixture_kernels: dict = {}
    fixture_protocols: dict = {}
    fixture_planes: dict = {}
    for mod_path in args.audit_module:
        mod = _load_audit_module(mod_path)
        fixture_drivers.update(getattr(mod, "HARPLINT_DRIVERS", {}))
        fixture_kernels.update(getattr(mod, "HARPLINT_KERNELS", {}))
        fixture_protocols.update(getattr(mod, "HARPLINT_PROTOCOLS", {}))
        fixture_planes.update(getattr(mod, "HARPLINT_PLANES", {}))

    if args.layer in ("threads", "all"):
        # pure ast — no backend, no jax import; --changed scopes to the
        # planes owning the changed files (graphs are cached per plane)
        if fixture_mode:
            if fixture_planes:
                violations += run_threads_layer(fixture_planes, repo)
        else:
            from harp_tpu.analysis.threadgraph import planes_for_paths

            only = (planes_for_paths(changed_rels)
                    if changed_rels is not None else None)
            violations += run_threads_layer(None, repo, only=only)

    if args.layer in ("jaxpr", "all"):
        if fixture_mode:
            if fixture_drivers:
                violations += run_jaxpr_layer(fixture_drivers, threshold)
        else:
            _force_cpu_backend()
            from harp_tpu.analysis.drivers import DRIVERS

            violations += run_jaxpr_layer(DRIVERS, threshold)

    if args.layer in ("mosaic", "all"):
        if fixture_mode:
            if fixture_kernels:
                violations += run_mosaic_layer(fixture_kernels)
        else:
            _force_cpu_backend()
            violations += run_mosaic_layer(None)

    byte_sheets: dict | None = None
    if args.layer in ("commgraph", "all"):
        if fixture_mode:
            if fixture_drivers:
                vs, _ = run_commgraph_layer(fixture_drivers)
                violations += vs
            if fixture_protocols:
                violations += run_protocol_layer(fixture_protocols)
        else:
            _force_cpu_backend()
            from harp_tpu.analysis.drivers import DRIVERS, PROTOCOLS

            vs, byte_sheets = run_commgraph_layer(DRIVERS)
            violations += vs
            violations += run_protocol_layer(PROTOCOLS)

    entries = [] if args.no_allowlist else allowlist_mod.load(args.allowlist)
    kept, suppressed, stale = allowlist_mod.apply(violations, entries)
    # staleness only means something when every layer swept everything:
    # a fixture run or a --changed AST scope cannot prove an entry dead,
    # and a --layer run can only judge entries of the layers that ran
    # (an AST-only run matching no HL4xx entry proves nothing about it)
    if fixture_mode or args.changed:
        stale = []
    elif args.layer != "all":
        stale = [e for e in stale
                 if RULES.get(e["rule"]) is not None
                 and RULES[e["rule"]].layer == args.layer]

    row = build_row(kept, suppressed, stale, scanned, byte_sheets)
    from harp_tpu.utils.metrics import benchmark_json

    if not args.json:
        print(render(kept, suppressed, stale, scanned))
    print(benchmark_json("lint", row), flush=True)
    # stale allowlist entries are a hard failure (same exit as an
    # unallowlisted violation): an exception excusing nothing either
    # outlived its fix or was always wrong — both need a human
    return 1 if kept or stale else 0


if __name__ == "__main__":
    sys.exit(main())
