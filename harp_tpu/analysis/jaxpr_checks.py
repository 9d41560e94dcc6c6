"""Layer 2 — jaxpr analyzers: trace on the CPU backend, zero hardware.

Two detectors over ``jax.jit(fn).trace(...).jaxpr`` (a ``ClosedJaxpr``):

**Scan-carry copy trap** (HL101) — a ``scan``/``while`` whose body both
gathers from and ``dynamic_update_slice``s the same carried array forces
XLA to copy the WHOLE table every iteration (the aliasing analysis cannot
prove the gather reads pre-update values).  This exact pattern cost LDA
20 s of a 29 s epoch before the tile-local fix (CLAUDE.md "XLA copy
trap"); the fixed form — ``dynamic_slice`` the tile first, gather
tile-locally — is clean because the gather operand is the slice result,
not the carry.  Taint propagates through dtype casts and into inner
call jaxprs (``jnp.take`` hides its gather inside a ``pjit``), but NOT
through ``dynamic_slice``: that boundary is precisely what makes the
fixed form safe.

**Oversized closed-over constant** (HL102) — arrays captured by value
into the jaxpr's ``consts`` ship as compile-time literals: the bytes
are embedded in the executable (and in its persistent-cache entry), and
the program recompiles every time the host value changes.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from harp_tpu.analysis import Violation

# 1 MiB: generous for genuine epsilon tables / iota caches — anything
# bigger should be an argument
DEFAULT_CONST_BYTES = 1 << 20

_GATHER_PRIMS = frozenset({"gather", "dynamic_slice_with_gather"})
_DUS_PRIMS = frozenset({"dynamic_update_slice", "scatter", "scatter-add",
                        "scatter_add"})
# ops that forward the carried buffer itself (not a copy/slice of it)
_PASSTHROUGH_PRIMS = frozenset({"convert_element_type", "copy",
                                "optimization_barrier"})


def _is_var(v) -> bool:
    """jaxpr invars mix Vars with (unhashable) Literals; only Vars can
    carry taint."""
    return not hasattr(v, "val")


def _inner_jaxprs(eqn):
    """(param_name, jaxpr) pairs hiding inside an eqn's params."""
    out = []
    for k, v in eqn.params.items():
        core = getattr(v, "jaxpr", None)      # ClosedJaxpr
        if core is not None and hasattr(core, "eqns"):
            out.append((k, core))
        elif hasattr(v, "eqns"):              # bare Jaxpr
            out.append((k, v))
    return out


def _body_flags(jaxpr, tainted: set) -> tuple[bool, bool]:
    """(gathers_from_tainted, dus_into_tainted) over a body jaxpr,
    recursing into inner call jaxprs with positional invar mapping."""
    gathered = dused = False
    tainted = set(tainted)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        op0 = eqn.invars[0] if eqn.invars else None
        hot = op0 is not None and _is_var(op0) and op0 in tainted
        if name in _GATHER_PRIMS and hot:
            gathered = True
        elif name in _DUS_PRIMS and hot:
            dused = True
        elif name in _PASSTHROUGH_PRIMS and hot:
            tainted.add(eqn.outvars[0])
        for _, inner in _inner_jaxprs(eqn):
            if len(inner.invars) != len(eqn.invars):
                continue  # boundary with repacked args: stop the taint
            inner_taint = {iv for iv, ov in zip(inner.invars, eqn.invars)
                           if _is_var(ov) and ov in tainted}
            if inner_taint:
                g, d = _body_flags(inner, inner_taint)
                gathered |= g
                dused |= d
    return gathered, dused


def _eqn_loc(eqn) -> str:
    """The user frame of an eqn (for the violation message)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is not None:
        return f"{frame.file_name}:{frame.start_line}"
    return "?"


def find_scan_copy_traps(closed_jaxpr, target: str = "jaxpr"
                         ) -> list[Violation]:
    """HL101 over every scan/while (at any nesting depth) in a traced
    program.  ``target`` labels the program in the violation's path."""
    out: list[Violation] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                nc = eqn.params["num_consts"]
                ncarry = eqn.params["num_carry"]
                carries = set(body.invars[nc:nc + ncarry])
                _flag(eqn, body, carries)
            elif name == "while":
                body = eqn.params["body_jaxpr"].jaxpr
                nconsts = eqn.params.get("body_nconsts", 0)
                carries = set(body.invars[nconsts:])
                _flag(eqn, body, carries)
            # nested scans are reached here too: a scan's body jaxpr is
            # one of its param jaxprs
            for _, inner in _inner_jaxprs(eqn):
                walk(inner)

    def _flag(eqn, body, carries):
        # per-carry attribution: one finding per carried buffer that is
        # both gathered from and updated in place
        for c in carries:
            g, d = _body_flags(body, {c})
            if g and d:
                out.append(Violation(
                    "HL101", f"{target}", 0,
                    f"scan/while body at {_eqn_loc(eqn)} gathers from AND "
                    f"dynamic_update_slices the same carried array "
                    f"{c.aval.str_short()} — XLA will copy the whole "
                    "table every iteration; dynamic_slice the tile "
                    "first, gather tile-locally"))

    walk(closed_jaxpr.jaxpr)
    return out


def find_large_constants(closed_jaxpr, target: str = "jaxpr",
                         threshold_bytes: int = DEFAULT_CONST_BYTES
                         ) -> list[Violation]:
    """HL102: closed-over array constants above ``threshold_bytes``."""
    out: list[Violation] = []
    for c in closed_jaxpr.consts:
        if not hasattr(c, "dtype"):
            continue  # a Python scalar constant
        # size × itemsize, not .nbytes: jax 0.9.0 closes over a
        # TypedNdArray, which has shape/dtype/size and no nbytes
        nbytes = int(c.size) * np.dtype(c.dtype).itemsize
        if nbytes > threshold_bytes:
            out.append(Violation(
                "HL102", target, 0,
                f"closed-over constant {c.dtype}{list(c.shape)} = "
                f"{nbytes / (1 << 20):.1f} MiB ships as a compile-time "
                f"literal (threshold {threshold_bytes >> 20} MiB): it is "
                "re-embedded in every compile of the program and bloats "
                "the executable and the persistent-cache entry — pass it "
                "as an argument via device_put/shard_array"))
    return out


def trace_for_analysis(fn, *args, **kwargs) -> Any:
    """``jax.jit(fn).trace(*args).jaxpr`` — the one tracing entry point
    (accepts concrete arrays or ShapeDtypeStructs; runs on whatever
    backend is active — the CLI forces CPU first)."""
    import jax

    return jax.jit(fn).trace(*args, **kwargs).jaxpr


def analyze_program(fn, args, target: str,
                    threshold_bytes: int = DEFAULT_CONST_BYTES
                    ) -> list[Violation]:
    """Run both Layer-2 detectors over one traced program."""
    closed = fn.trace(*args).jaxpr if hasattr(fn, "trace") \
        else trace_for_analysis(fn, *args)
    return (find_scan_copy_traps(closed, target)
            + find_large_constants(closed, target, threshold_bytes))
