"""Layer 4 — CommGraph: the static communication-graph auditor.

Reference parity (SURVEY.md §6, ROADMAP "topology-aware collective
planner"): Harp's collective schedule exists only at runtime, inside
Netty socket handlers; harp-tpu's CommLedger (PR 1) made the schedule
*observable* at trace time, but still only as a side effect of running
the tracer.  TACCL (PAPERS.md arXiv:2111.04867) synthesizes collective
algorithms from exactly the artifact this module extracts: a static,
program-level *communication sketch* — the ordered collective schedule
with per-call-site payloads and loop amplification.  The extractor walks
each registered driver jaxpr (:mod:`harp_tpu.analysis.drivers`) through
``pjit``/``shard_map``/``scan``/``while``/``cond`` boundaries and emits
one :class:`CommGraph` per program; ``python -m harp_tpu lint`` ships
every program's byte sheet in its JSON row — the planner's future input.

The same walk closes the two audit gaps no earlier layer sees:

**HL301 / HL302 — the ledger cross-check.**  Extraction traces the
program with telemetry enabled, so the CommLedger records land next to
the static schedule.  Both sides key call sites identically
(:func:`harp_tpu.utils.telemetry.site_key` over the nearest frame that
:func:`~harp_tpu.utils.telemetry.is_ledger_user_frame` accepts — the
verbs' ``record_comm`` walks the live stack, this module walks the jaxpr
eqn's traceback).  A static collective with no ledger record at its site
is an untracked wire (HL301 — today the ledger can under-report and
nothing notices); a matched *exact-wire* site whose static per-shard
bytes disagree with the ledger payload is a lying byte sheet (HL302 —
the kmeans hand-computed sheet is the pinned fixture).  Quantized sites
(ledger ``wire_dtype`` set) skip the byte comparison: the ledger counts
the *logical* wire (int8 = 1 B/elem) while the lowering accumulates in
int32 — a documented, deliberate divergence.

**HL304 — hoistable collectives.**  A collective inside a loop body
whose operands depend on neither the carry nor the scanned inputs moves
identical bytes every iteration; the loop's static trip count multiplies
the wire for nothing.  Detected by forward taint from each loop's
variant invars, positionally mapped through inner call boundaries.

**HL303 — use-after-donate** is a *host-protocol* hazard, not a jaxpr
property: the serve engines donate their batch buffer
(``donate_argnums``), the CPU sim ignores donation (so tests stay
green), and silicon does not.  :class:`DonationAudit` wraps the
donating executables of a real driven pipeline (the registered
``PROTOCOLS`` in drivers.py run the serve ``ContinuousRunner`` depth-2
loop at lint time) and flags any donated buffer that is later
re-dispatched or read back through :func:`harp_tpu.utils.flightrec.
readback` — the counted D2H path all driver code uses.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from harp_tpu.analysis import Violation


def _collective_prims() -> frozenset:
    from harp_tpu.parallel.collective import COLLECTIVE_PRIMS

    return COLLECTIVE_PRIMS


# ---------------------------------------------------------------------------
# The graph
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CommSite:
    """One call site's collective traffic in one program (possibly
    several jaxpr eqns: a pytree verb emits one primitive per leaf)."""

    site: str               # telemetry.site_key shape ("kmeans.py:324")
    primitive: str          # jaxpr primitive name ("psum", "ppermute"...)
    axis: str               # mesh axis name(s) the collective runs over
    path: str               # enclosing-structure trail ("shard_map/scan")
    shapes: list[str]       # operand aval short-strings, in eqn order
    wire_dtype: str         # lowered operand dtype of the first eqn
    per_shard_bytes: int    # per-execution operand bytes, summed over eqns
    calls_per_trace: int    # number of eqns folded into this record
    amplification: int      # product of enclosing static trip counts
    dynamic: bool           # inside a while loop (trip count unknown)
    in_loop: bool           # inside any scan/while body
    loop_invariant: bool    # no operand depends on a loop-variant value
    verb: str | None = None          # matched CommLedger verb
    ledger_wire: str | None = None   # matched ledger wire_dtype

    def row(self) -> dict:
        return {
            "site": self.site, "primitive": self.primitive,
            "verb": self.verb, "axis": self.axis,
            "wire_dtype": self.wire_dtype,
            "per_shard_bytes": self.per_shard_bytes,
            "calls_per_trace": self.calls_per_trace,
            "amplification": self.amplification,
            "dynamic": self.dynamic, "path": self.path,
        }


@dataclasses.dataclass
class CommGraph:
    """One program's static communication sketch + donation aliasing."""

    program: str
    sites: list[CommSite]               # schedule order (first appearance)
    donated_args: list[int]             # flat arg indices with donation
    donated_avals: list[str]            # their aval short-strings
    ledger_sites: dict[str, list[dict]]  # site key -> trace-time records

    def bytes_per_trace(self) -> int:
        return sum(s.per_shard_bytes for s in self.sites)

    def amplified_bytes(self) -> int:
        """Per-program-execution wire bytes: each site's payload times
        its enclosing static trip counts (dynamic loops count once and
        carry the ``dynamic`` flag — a floor, not a total)."""
        return sum(s.per_shard_bytes * max(s.amplification, 1)
                   for s in self.sites)

    def sheet(self) -> dict:
        """The machine-readable byte sheet the lint JSON row carries —
        scripts/check_jsonl.py invariant 6 validates its shape."""
        return {
            "collectives": [s.row() for s in self.sites],
            "bytes_per_trace": self.bytes_per_trace(),
            "amplified_bytes": self.amplified_bytes(),
            "donated_args": list(self.donated_args),
            "donated_avals": list(self.donated_avals),
        }


# ---------------------------------------------------------------------------
# Extraction
# ---------------------------------------------------------------------------

def _is_var(v) -> bool:
    return not hasattr(v, "val")  # Literals carry .val, Vars do not


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * dtype.itemsize


def _eqn_axis(eqn) -> str:
    ax = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(ax, (tuple, list)):
        return ",".join(str(a) for a in ax)
    return str(ax)


def _eqn_site(eqn) -> str:
    """The eqn's user call site, under the SAME frame-exclusion rules as
    the CommLedger's ``record_comm`` — the whole point of the matcher."""
    from jax._src import source_info_util

    from harp_tpu.utils.telemetry import is_ledger_user_frame, site_key

    for f in source_info_util.user_frames(eqn.source_info.traceback):
        if is_ledger_user_frame(f.file_name):
            return site_key(f.file_name, f.start_line)
    return "?:0"


def _map_taint(inner_invars, outer_invars, tainted: set) -> set:
    return {iv for iv, ov in zip(inner_invars, outer_invars)
            if _is_var(ov) and ov in tainted}


def _generic_inner_jaxprs(eqn):
    """Core jaxprs hiding in an eqn's params (pjit/shard_map/custom_*),
    for primitives without special-cased control flow."""
    out = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            core = getattr(item, "jaxpr", None)
            if core is not None and hasattr(core, "eqns"):
                out.append(core)
            elif hasattr(item, "eqns"):
                out.append(item)
    return out


class _Walker:
    def __init__(self):
        self.entries: list[CommSite] = []
        self._prims = _collective_prims()

    def walk(self, jaxpr, *, mult: int, dynamic: bool, in_loop: bool,
             tainted: set, path: str) -> None:
        tainted = set(tainted)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            hot = any(_is_var(v) and v in tainted for v in eqn.invars)
            if name in self._prims:
                self._record(eqn, name, mult, dynamic, in_loop, path,
                             loop_invariant=in_loop and not hot)
            if name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                length = int(eqn.params.get("length") or 1)
                nc = eqn.params["num_consts"]
                inner_t = _map_taint(body.invars, eqn.invars, tainted)
                inner_t |= set(body.invars[nc:])  # carries + xs slices
                self.walk(body, mult=mult * length, dynamic=dynamic,
                          in_loop=True, tainted=inner_t,
                          path=path + "/scan")
            elif name == "while":
                for key, nck in (("cond_jaxpr", "cond_nconsts"),
                                 ("body_jaxpr", "body_nconsts")):
                    bj = eqn.params[key].jaxpr
                    nc = eqn.params.get(nck, 0)
                    # while invars = cond_consts + body_consts + carries;
                    # positional zip only lines up for the jaxpr whose
                    # consts lead, so taint conservatively: carries are
                    # variant either way
                    inner_t = set(bj.invars[nc:])
                    self.walk(bj, mult=mult, dynamic=True, in_loop=True,
                              tainted=inner_t, path=path + "/while")
            elif name == "cond":
                for br in eqn.params["branches"]:
                    bj = getattr(br, "jaxpr", br)
                    inner_t = _map_taint(bj.invars, eqn.invars[1:],
                                         tainted)
                    self.walk(bj, mult=mult, dynamic=dynamic,
                              in_loop=in_loop, tainted=inner_t,
                              path=path + "/cond")
            else:
                for inner in _generic_inner_jaxprs(eqn):
                    if len(inner.invars) == len(eqn.invars):
                        inner_t = _map_taint(inner.invars, eqn.invars,
                                             tainted)
                    else:
                        # repacked boundary: conservative — everything
                        # variant if any operand is (never misses a
                        # variant dependency, may miss a hoist)
                        inner_t = set(inner.invars) if hot else set()
                    self.walk(inner, mult=mult, dynamic=dynamic,
                              in_loop=in_loop, tainted=inner_t,
                              path=path + "/" + name)
            if hot:
                tainted.update(eqn.outvars)

    def _record(self, eqn, name, mult, dynamic, in_loop, path,
                loop_invariant):
        site = _eqn_site(eqn)
        nbytes = sum(_aval_bytes(v) for v in eqn.invars)
        shape = [getattr(getattr(v, "aval", None), "str_short",
                         lambda: "?")() for v in eqn.invars]
        dtype = next((str(getattr(getattr(v, "aval", None), "dtype", ""))
                      for v in eqn.invars
                      if getattr(getattr(v, "aval", None), "dtype", None)
                      is not None), "?")
        for e in self.entries:
            if (e.site == site and e.primitive == name and e.path == path
                    and e.amplification == mult and e.dynamic == dynamic
                    and e.loop_invariant == loop_invariant):
                e.per_shard_bytes += nbytes
                e.calls_per_trace += 1
                e.shapes.extend(shape)
                return
        self.entries.append(CommSite(
            site=site, primitive=name, axis=_eqn_axis(eqn), path=path,
            shapes=shape, wire_dtype=dtype, per_shard_bytes=nbytes,
            calls_per_trace=1, amplification=mult, dynamic=dynamic,
            in_loop=in_loop, loop_invariant=loop_invariant))


def _donation_info(traced) -> tuple[list[int], list[str]]:
    """Flat donated-arg indices + avals from a ``.trace()`` result's
    ``args_info`` (ArgInfo carries the ``donated`` flag)."""
    import jax

    flat = jax.tree.leaves(traced.args_info)
    idx = [i for i, a in enumerate(flat) if a.donated]
    # ArgInfo stores its aval as _aval (no public accessor)
    return idx, [flat[i]._aval.str_short() for i in idx]


def extract(name: str, fn, args) -> CommGraph:
    """Trace one driver program (CommLedger enabled, so the trace-time
    records land beside the static walk) and extract its CommGraph."""
    import jax

    from harp_tpu.utils import telemetry as T

    with T.scope():
        with T.ledger.run(name, steps=0):
            traced = (fn.trace(*args) if hasattr(fn, "trace")
                      else jax.jit(fn).trace(*args))
        ledger_sites: dict[str, list[dict]] = {}
        tag = T.ledger.summary().get(name, {"sites": []})
        for rec in tag["sites"]:
            ledger_sites.setdefault(rec["site"], []).append(rec)

    donated, donated_avals = _donation_info(traced)
    walker = _Walker()
    closed = traced.jaxpr
    walker.walk(closed.jaxpr, mult=1, dynamic=False, in_loop=False,
                tainted=set(), path="")
    graph = CommGraph(program=name, sites=walker.entries,
                      donated_args=donated, donated_avals=donated_avals,
                      ledger_sites=ledger_sites)
    _match_ledger(graph)
    return graph


def _match_ledger(graph: CommGraph) -> None:
    """Attach the matched ledger verb/wire to each static site."""
    from harp_tpu.parallel.collective import PRIMITIVE_VERBS

    for s in graph.sites:
        recs = graph.ledger_sites.get(s.site)
        if not recs:
            continue
        allowed = PRIMITIVE_VERBS.get(s.primitive, ())
        rec = next((r for r in recs if r["verb"] in allowed), recs[0])
        s.verb = rec["verb"]
        s.ledger_wire = rec["wire_dtype"]


# ---------------------------------------------------------------------------
# Checks (HL301 / HL302 / HL304)
# ---------------------------------------------------------------------------

def check_graph(graph: CommGraph) -> list[Violation]:
    out: list[Violation] = []
    target = f"driver:{graph.program}"

    by_site: dict[str, list[CommSite]] = {}
    for s in graph.sites:
        by_site.setdefault(s.site, []).append(s)

    for site, entries in by_site.items():
        recs = graph.ledger_sites.get(site)
        if not recs:
            prims = sorted({e.primitive for e in entries})
            nbytes = sum(e.per_shard_bytes for e in entries)
            out.append(Violation(
                "HL301", target, 0,
                f"collective(s) {prims} at {site} ({nbytes} B/shard per "
                "trace) have no CommLedger record — an untracked wire "
                "the report's bytes-on-wire claims never see; route the "
                "call through a harp_tpu.parallel.collective verb"))
            continue
        if all(r["wire_dtype"] is None for r in recs):
            static_bytes = sum(e.per_shard_bytes for e in entries)
            ledger_bytes = sum(r["payload_bytes"] for r in recs)
            if static_bytes != ledger_bytes:
                verbs = sorted({r["verb"] for r in recs})
                out.append(Violation(
                    "HL302", target, 0,
                    f"static byte sheet disagrees with the ledger at "
                    f"{site}: jaxpr operands move {static_bytes} B/shard "
                    f"per trace but the CommLedger recorded "
                    f"{ledger_bytes} B for {verbs} — one sheet is lying "
                    "(quantized wires are exempt; exact verbs must "
                    "agree to the byte)"))

    for s in graph.sites:
        if s.in_loop and s.loop_invariant and not s.dynamic:
            out.append(Violation(
                "HL304", target, 0,
                f"loop-invariant {s.primitive} at {s.site} (inside "
                f"{s.path or '/'}, trip count {s.amplification}) — its "
                f"operands depend on neither the carry nor the scanned "
                f"inputs, so {s.per_shard_bytes} B/shard re-ship every "
                "iteration; hoist the collective above the loop"))
        elif s.in_loop and s.loop_invariant and s.dynamic:
            out.append(Violation(
                "HL304", target, 0,
                f"loop-invariant {s.primitive} at {s.site} inside a "
                f"while loop ({s.path or '/'}) — identical bytes every "
                "iteration of a dynamic loop; hoist it above the loop"))
    return out


def analyze_program(name: str, fn, args) -> tuple[list[Violation],
                                                  CommGraph]:
    """Extract + check one program (the CLI's per-driver entry)."""
    graph = extract(name, fn, args)
    return check_graph(graph), graph


# ---------------------------------------------------------------------------
# HL303 — the donation audit
# ---------------------------------------------------------------------------

class DonationAudit:
    """Use-after-donate protocol recorder (HL303).

    Wrap each donating executable with :meth:`wrap`; run the host loop
    inside the audit's context (which watches
    :func:`harp_tpu.utils.flightrec.readback`, the counted D2H path).
    After a buffer rides a donated argument position, any later
    appearance — as an argument to ANY wrapped executable, or as a
    readback operand — is a violation.  Object identity is the buffer
    key; the audit holds a reference to every donated buffer so ids are
    never recycled within a run.

    The CPU sim *ignores* donation (XLA warns "Some donated buffers were
    not usable"), which is exactly why this must be a lint-time check:
    a host loop that re-reads a donated buffer passes every CPU test and
    dies (or silently reads freed memory) the first time it runs on TPU.
    """

    def __init__(self, target: str):
        self.target = target
        self.violations: list[Violation] = []
        self._donated: dict[int, str] = {}   # id(buffer) -> donor label
        self._keep: list[Any] = []           # pin ids for the run

    # -- wiring ------------------------------------------------------------
    def wrap(self, exe: Callable, donate_argnums: tuple[int, ...],
             label: str) -> Callable:
        """Wrap a donating callable: flags donated args re-dispatched
        through ANY wrapped callable, then marks this call's donated
        positions.  Delegates every other attribute (``lower``,
        ``trace``, ...) like ``flightrec.track``'s wrapper."""
        return _DonationWrapped(self, exe, tuple(donate_argnums), label)

    def __enter__(self):
        from harp_tpu.utils import flightrec

        self._obs = flightrec.observe_readbacks(self._note_readback)
        self._obs.__enter__()
        return self

    def __exit__(self, *exc):
        self._obs.__exit__(*exc)
        return False

    # -- events ------------------------------------------------------------
    def _note_readback(self, x: Any) -> None:
        donor = self._donated.get(id(x))
        if donor is not None:
            self._flag(f"host read (flightrec.readback) of a buffer "
                       f"donated to {donor} — on TPU that buffer no "
                       "longer exists; read the dispatch OUTPUT, stage "
                       "a fresh input per batch")

    def _note_dispatch(self, label: str, args: tuple,
                       donate_argnums: tuple[int, ...]) -> None:
        for pos, a in enumerate(args):
            donor = self._donated.get(id(a))
            if donor is not None:
                self._flag(f"arg {pos} of {label} was already donated "
                           f"to {donor} — a donated buffer cannot be "
                           "re-dispatched; stage a fresh buffer per "
                           "batch")
        for pos in donate_argnums:
            if pos < len(args):
                self._donated[id(args[pos])] = label
                self._keep.append(args[pos])

    def _flag(self, msg: str) -> None:
        self.violations.append(Violation("HL303", self.target, 0, msg))


class _DonationWrapped:
    __slots__ = ("_audit", "__wrapped__", "_donate", "_label")

    def __init__(self, audit: DonationAudit, exe: Callable,
                 donate_argnums: tuple[int, ...], label: str):
        self._audit = audit
        self.__wrapped__ = exe
        self._donate = donate_argnums
        self._label = label

    def __call__(self, *args, **kw):
        self._audit._note_dispatch(self._label, args, self._donate)
        return self.__wrapped__(*args, **kw)

    def __getattr__(self, name):
        return getattr(self.__wrapped__, name)


def audit_protocol(name: str, drive: Callable[[DonationAudit], None]
                   ) -> list[Violation]:
    """Run one registered host protocol under a :class:`DonationAudit`
    (the CLI's HL303 entry; ``drive`` wraps its donating executables via
    ``audit.wrap`` and runs the real pipeline on the CPU mesh)."""
    audit = DonationAudit(f"protocol:{name}")
    try:
        with audit:
            drive(audit)
    except Exception as e:  # noqa: BLE001 - a broken protocol is loud
        audit._flag(f"protocol run failed: {type(e).__name__}: {e}")
    return audit.violations
