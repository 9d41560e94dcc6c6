"""Device time by the program's own names: the traced run's ``XLA Ops``
put down to the ``jax.named_scope``s the program wrote around its parts.

A v5e device event is named by its whole HLO line and carries no
``op_name``; the program's telemetry keeps, for each tracked program, the
map from an instruction of its optimized HLO to the ``op_name`` that holds
the scopes (``telemetry.scopes``, filled in the traced run alone).  The
device plane's line ``XLA Modules`` has one event per executed program,
named ``<HLO module>(<fingerprint>)``: an op belongs to the program whose
event holds it in time.

A scope is a ``/``-separated segment of the ``op_name`` that is lower
case and holds a dot, each part beginning with a letter, once
transformation wrappers are peeled: no JAX primitive or transformation
name has a dot, and the names XLA gives its own instructions
(``broadcast.25``) have a number after theirs.  ``jit(f)/jvp(mlp.layer1)/
dot_general`` has the path ``mlp.layer1``; ``.../transpose(jvp(mlp.
layer1))/...`` the same, marked backward.

Nothing here is read by a program without the map (one from before the
PR that added it), by a run without a trace, or by a trace without a
``/device:TPU:`` plane: :func:`by_scope` gives ``None``.
"""

from __future__ import annotations

import bisect
import os
import re

from perf import harness, spec, trace_reduce

MODULES_LINE = "XLA Modules"
UNSCOPED_TOP, SCOPE_TOP = 10, 3

_WRAPPER = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")
_SCOPE = re.compile(r"^[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+$")
_NOT_READ = object()


def scope_path(op_name: str | None) -> tuple[tuple[str, ...], bool]:
    """``(scopes, backward)`` of one ``op_name``: its segments,
    transformation wrappers peeled (``jvp(..)``, ``transpose(..)``,
    ``vmap(..)``, ``checkpoint(..)``; ``jit(..)`` and ``pjit(..)`` hold a
    function's name, never a scope), that are scope names.  ``backward``:
    a ``transpose(`` was peeled somewhere on the path."""
    path, backward = [], False
    for seg in (op_name or "").split("/"):
        wrappers = []
        while (m := _WRAPPER.match(seg)) is not None:
            wrappers.append(m.group(1))
            seg = m.group(2)
        backward = backward or "transpose" in wrappers
        if not {"jit", "pjit"} & set(wrappers) and _SCOPE.match(seg):
            path.append(seg)
    return tuple(path), backward


def _planes(pd):
    """Per device plane: its ``XLA Ops`` as ``(name, start, end)`` and its
    ``XLA Modules`` as ``(starts, ends, names)``, sorted by start; a
    module's name is its event's up to ``(``."""
    programs = {
        plane.name: sorted(
            (float(e.start_ns), float(e.start_ns + e.duration_ns),
             e.name.partition("(")[0]) for e in line.events)
        for plane in pd.planes for line in plane.lines
        if line.name == MODULES_LINE}
    for plane_name, ops in trace_reduce._device_lines(pd):
        ran = programs.get(plane_name, [])
        yield ops, ([m[0] for m in ran], [m[1] for m in ran],
                    [m[2] for m in ran])


def _resolver(modules: dict):
    """``(module or None, op text) -> (path, backward) or None``, each
    distinct pair worked out once.  Where the module line gives nothing,
    an instruction that exactly one module's map holds is taken from it."""
    owners: dict[str, list[str]] = {}
    for module, instructions in modules.items():
        for name in instructions:
            owners.setdefault(name, []).append(module)
    known: dict = {}

    def resolve(module, text):
        key = (module, text)
        if key not in known:
            name = trace_reduce.parse_op(text)[0]
            if module not in modules and len(owners.get(name, ())) == 1:
                module = owners[name][0]
            op_name = modules.get(module, {}).get(name)
            known[key] = None if op_name is None else scope_path(op_name)
        return known[key]

    return resolve


def reduce(pd, modules: dict) -> dict | None:
    """Self time of the traced window's device ops by scope; ``modules``
    is ``telemetry.scopes.modules``.  Seconds, mean over device planes as
    in :func:`trace_reduce.reduce`; containers (``while``, ``call``,
    ``conditional``) are skipped as there, so ``busy_s`` here is the sum of
    the ops' self times, what the parts below add up to:

    ``by_path``     ``"subgraph.sum.leaf/subgraph.tail"`` -> s
    ``by_scope``    every scope -> s of the ops that have it anywhere on
                    their path (``subgraph.tail`` sums both shapes)
    ``backward_s``  the same for the ops under a ``transpose(..)``
    ``unscoped_s``  ops the map lacks or that lie under no scope, and
                    ``unscoped_ops`` the largest of them
    ``top_ops``     per scope its largest ops, ``[short name, s]``

    ``None`` for a trace without a device plane."""
    planes = list(_planes(pd))
    if not planes:
        return None
    windows = [(s, e) for n, s, e in trace_reduce._host_spans(pd)
               if n == trace_reduce.WINDOW_SPAN]
    t0, t1 = windows[0] if windows else (float("-inf"), float("inf"))
    resolve = _resolver(modules)
    by_path: dict = {}
    by_scope: dict = {}
    backward_s: dict = {}
    ops_of: dict = {}     # scope (None: unscoped) -> {short name: ns}
    busy = unscoped = 0.0
    for ops, (starts, ends, names) in planes:
        events = trace_reduce._clip(ops, t0, t1)
        for (text, start, _), (_, self_ns) in zip(
                events, trace_reduce.self_times(events)):
            if trace_reduce._is_container(text):
                continue
            i = bisect.bisect_right(starts, start) - 1
            module = names[i] if i >= 0 and start < ends[i] else None
            found = resolve(module, text)
            path, backward = found or ((), False)
            busy += self_ns
            short = trace_reduce.short_name(text)
            for scope in set(path) or (None,):
                per = ops_of.setdefault(scope, {})
                per[short] = per.get(short, 0.0) + self_ns
                if scope is not None:
                    by_scope[scope] = by_scope.get(scope, 0.0) + self_ns
                    if backward:
                        backward_s[scope] = (backward_s.get(scope, 0.0)
                                             + self_ns)
            if path:
                key = "/".join(path)
                by_path[key] = by_path.get(key, 0.0) + self_ns
            else:
                unscoped += self_ns
    per_s = 1e-9 / len(planes)

    def seconds(d):
        return {k: v * per_s for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    def largest(scope, top):
        return [[k, v] for k, v in
                list(seconds(ops_of.get(scope, {})).items())[:top]]

    return {
        "busy_s": busy * per_s,
        "by_path": seconds(by_path),
        "by_scope": seconds(by_scope),
        "backward_s": seconds(backward_s),
        "unscoped_s": unscoped * per_s,
        "unscoped_ops": largest(None, UNSCOPED_TOP),
        "top_ops": {scope: largest(scope, SCOPE_TOP) for scope in by_scope},
        "n_devices": len(planes),
    }


def by_scope(run) -> dict | None:
    """:func:`reduce` of the run's own trace against the program's map,
    one pass a run whatever number of readers ask; the first call prints
    the table as an ``info {"scopes": ...}`` line.  ``None`` where there is
    nothing to read (see the module's header)."""
    cached = getattr(run, "_by_scope", _NOT_READ)
    if cached is not _NOT_READ:
        return cached
    run._by_scope = table = _read(run)
    if table is not None:
        print("info " + spec.dumps({"scopes": table}), flush=True)
    return table


def _read(run) -> dict | None:
    from harp_tpu.utils import telemetry

    scopes = getattr(telemetry, "scopes", None)
    if run.trace is None or scopes is None or not scopes.modules:
        return None
    try:
        path = trace_reduce.find_xplane(os.path.join(
            run.cell.root, harness.TRACE_DIR, run.cell.name))
    except FileNotFoundError:
        return None
    return reduce(trace_reduce.load(path), scopes.modules)


def share(run, *scopes: str) -> float | None:
    """Percent of the busy self time under the given scopes together (no
    op may lie under two of them, or it counts twice)."""
    table = by_scope(run)
    if not table or not table["busy_s"]:
        return None
    return 100.0 * sum(table["by_scope"].get(s, 0.0)
                       for s in scopes) / table["busy_s"]
