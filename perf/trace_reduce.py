"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, self time by op and by class of op, and
the idle gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a v5e
trace of jax 0.9.0 holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per executed
HLO op, named by its whole HLO line and nested where an op contains
others (a ``while`` spans its body);
one plane ``/host:CPU`` whose lines are host threads, carrying the
``jax.profiler.TraceAnnotation`` spans.  Both are on one clock, in
nanoseconds from the start of the trace.

The benchmark's own spans are named ``perf:<what>``; the traced window
is the span ``perf:window``.  Everything is cut to that window.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "perf:"
WINDOW_SPAN = "perf:window"

# HLO collectives, by the op's name in the trace (the async pair
# ``-start``/``-done`` carries the same stem)
COLLECTIVE_STEMS = ("all-reduce", "all-gather", "all-to-all",
                    "collective-permute", "reduce-scatter",
                    "collective-broadcast")
# ops that only contain other ops: their own time is whatever their
# children leave uncovered
CONTAINER_STEMS = ("while", "conditional", "call")
MAX_BUSY = 4096


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under a ``jax.profiler`` output directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


_OPCODE = re.compile(r"[\s)]([a-z][a-z0-9\-]*)\(")


def parse_op(text: str) -> tuple[str, str]:
    """``(short name, opcode)`` of a device event.  A v5e trace names an
    op by its whole HLO line — ``%fusion.14 = (f32[..], ..) fusion(bf16[..]
    %x, ..), kind=kOutput, calls=..`` — so the name is what stands before
    `` = `` and the opcode the first lower-case word followed by ``(``
    after it (shapes hold ``T(8,128)`` and ``S(1)``, never a lower-case
    word before a bracket).  A bare name (``fusion.14``) gives its stem."""
    head, sep, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    if sep:
        m = _OPCODE.search(" " + rest)
        if m:
            return name, m.group(1)
    return name, name.split(".")[0]


def op_class(text: str) -> str:
    """``collective``, ``kernel`` (a Mosaic call) or ``xla``."""
    name, opcode = parse_op(text)
    for c in COLLECTIVE_STEMS:
        if opcode.startswith(c) or name.startswith(c):
            return "collective"
    if opcode == "custom-call" or "tpu_custom_call" in text:
        return "kernel"
    return "xla"


def short_name(text: str) -> str:
    """What the breakdown prints for an op: its name and, where the name
    does not say it, its opcode."""
    name, opcode = parse_op(text)
    if "tpu_custom_call" in text:
        opcode = "tpu_custom_call"
    return name if name.split(".")[0] == opcode else f"{name} [{opcode}]"


def _is_container(text: str) -> bool:
    return parse_op(text)[1] in CONTAINER_STEMS


def _union(intervals, slack: float = 0.0):
    """Merged, sorted ``[start, end]`` lists of possibly overlapping
    intervals; those less than ``slack`` apart count as touching."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1] + slack:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, t0, t1):
    """``(name, start, end)`` cut to ``[t0, t1]``; what falls outside
    goes."""
    out = []
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def self_times(events):
    """Self time per event of one device line: its duration less the
    part its nested children cover.  ``events`` is ``(name, start,
    end)``; returns ``[(name, self_ns)]``."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    covered = [0.0] * len(events)
    stack: list[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:  # direct parent loses this child's span
            covered[stack[-1]] += e - s
        stack.append(i)
    return [(events[i][0], max(events[i][2] - events[i][1] - covered[i],
                               0.0)) for i in range(len(events))]


def _device_lines(pd):
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                yield plane.name, [(e.name, float(e.start_ns),
                                    float(e.start_ns + e.duration_ns))
                                   for e in line.events]


def _host_spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns)))
    return spans


def _label_gap(g0, g1, spans):
    """The benchmark span that covers most of an idle gap; where spans
    nest, the innermost (shortest) of those that cover it best."""
    best, best_key = "unattributed", (0.0, 0.0)
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        key = (round(ov / (g1 - g0), 3), -(e - s))
        if key > best_key:
            best, best_key = name[len(SPAN_PREFIX):], key
    return best


def reduce(pd, top: int = 10) -> dict:
    """The reduction.  Seconds throughout; means are over device planes.

    ``window_s``   length of the ``perf:window`` span (or, without one,
                   from the first to the last device op)
    ``busy_s``     union of the op intervals inside the window
    ``class_s``    self time by class: ``xla``, ``kernel``, ``collective``
    ``device_ops`` the ``top`` ops by self time, ``[name, seconds]``
    ``idle_gaps``  idle time inside the window summed by the benchmark
                   span the host was in, ``[name, seconds]``, longest
                   first
    ``spans``      every ``perf:`` span inside the window, ``[name,
                   start_s, end_s]`` from the window's start
    ``busy``       the first device's busy intervals, ``[start_s, end_s]``
                   from the window's start (at most ``MAX_BUSY`` of them)
    ``n_devices``  device planes that carried ops
    """
    lines = list(_device_lines(pd))
    spans = _host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        t0, t1 = windows[0]
    elif any(ev for _, ev in lines):
        t0 = min(s for _, ev in lines for _, s, _ in ev)
        t1 = max(e for _, ev in lines for _, _, e in ev)
    else:
        t0 = t1 = 0.0
    spans = _clip(spans, t0, t1)
    busy, class_s, per_op, gaps, first_busy = [], {}, {}, {}, None
    for _, events in lines:
        events = _clip(events, t0, t1)
        if not events:
            continue
        merged = _union([(s, e) for _, s, e in events])
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            # ops of one program follow each other a nanosecond apart
            first_busy = _union(merged, slack=1000.0)[:MAX_BUSY]
        for name, ns in self_times(events):
            if _is_container(name):
                continue  # a loop's own time is bookkeeping between ops
            cls = op_class(name)
            class_s[cls] = class_s.get(cls, 0.0) + ns
            short = short_name(name)
            per_op[short] = per_op.get(short, 0.0) + ns
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = _label_gap(g0, g1, spans)
                gaps[label] = gaps.get(label, 0.0) + (g1 - g0)
    n = max(len(busy), 1)
    ns = 1e-9

    def ranked(d):
        return [[k, v * ns / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (t1 - t0) * ns,
        "busy_s": sum(busy) * ns / n,
        "class_s": {k: v * ns / n for k, v in class_s.items()},
        "device_ops": ranked(per_op),
        "idle_gaps": ranked(gaps),
        "spans": [[name, (s - t0) * ns, (e - t0) * ns]
                  for name, s, e in sorted(spans, key=lambda x: x[1])],
        "busy": [[(s - t0) * ns, (e - t0) * ns]
                 for s, e in first_busy or []],
        "n_devices": len(busy),
    }
