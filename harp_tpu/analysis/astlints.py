"""Layer 1 — source AST lints (pure ``ast``, no jax import).

Each checker encodes one CLAUDE.md trap (see :mod:`harp_tpu.analysis.rules`
for the id → trap map).  Everything here is static text analysis: the
whole repo lints in well under a second, so tier-1 runs it on every test
invocation and the lint CLI runs it with no backend at all.

Scoping is per rule, not per run: raw-collective calls are legal inside
the verb layer itself (``parallel/collective.py`` + ``parallel/rotate.py``),
``PRNGKey`` is legal inside the helper that wraps it (``utils/prng.py``),
and the flight-tracking rule only binds the driver layer
(``harp_tpu/models/``).  Intentional exceptions elsewhere go in
``analysis/allowlist.toml`` with a reviewed one-line justification —
never in code.
"""

from __future__ import annotations

import ast
import os
import re

from harp_tpu.analysis import Violation

# the data-moving XLA collectives the verb layer wraps; axis_index /
# axis_size are topology queries, not collectives, and stay legal
RAW_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pshuffle",
    "psum_scatter", "all_gather", "all_to_all",
})

# files where each rule does NOT apply (repo-relative, forward slashes)
HL001_EXEMPT = ("harp_tpu/parallel/collective.py",
                "harp_tpu/parallel/rotate.py")
HL002_EXEMPT = ("harp_tpu/utils/prng.py",)
HL004_SCOPE = ("harp_tpu/models/",)
HL005_SCOPE = ("harp_tpu/",)

# transfer entry points whose wrapping legitimizes a jnp.asarray (the
# array lands on device through a counted H2D path, not a jit literal)
_DEVICE_PUT_FUNCS = frozenset({"device_put", "shard_array",
                               "shard_array_local"})

# perf-claim shape: a measured rate ("246.5M ups/s", "2.45 ms/iter",
# "30-40 MB/s") or an explicit speedup-vs claim ("2.97× dense")
_PERF_RE = re.compile(
    r"\d[\d,.]*\s*[kKMG]?\s*"
    r"(?:iter|tok|ups|updates|points?|pts|rows|GB|MB)\s*/\s*(?:s\b|sec\b)"
    r"|\d[\d.,]*\s*ms\s*/\s*(?:iter|epoch|call)"
    # the repo writes measured speedups with the multiplication sign
    # ("2.97× dense"); ascii "1.6x the nonzeros" prose stays unflagged
    r"|\d[\d.]*\s*×\s*(?:dense|the|vs|faster|speedup|XLA)")
_DATE_RE = re.compile(r"20\d\d-\d\d-\d\d")
_CHIP_RE = re.compile(r"\bv[2-6][ep]?(?:-\d+)?\b|\bCPU\b|\bcpu\b|\bTPU\b"
                      r"|\bchip\b|\bhost\b")


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an Attribute/Name chain ("jax.lax.psum"), or ""."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _subtree_mentions_numpy(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in ("np", "numpy"):
            return True
    return False


class _Linter:
    def __init__(self, relpath: str, text: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=relpath)
        self.parents: dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.violations: list[Violation] = []

    # -- helpers -----------------------------------------------------------
    def _src(self, node: ast.AST) -> str:
        ln = getattr(node, "lineno", 0)
        return self.lines[ln - 1] if 0 < ln <= len(self.lines) else ""

    def _emit(self, rule: str, node: ast.AST, msg: str) -> None:
        self.violations.append(Violation(
            rule, self.relpath, getattr(node, "lineno", 0), msg,
            self._src(node)))

    def _ancestors(self, node: ast.AST):
        n = self.parents.get(node)
        while n is not None:
            yield n
            n = self.parents.get(n)

    def _in_call_to(self, node: ast.AST, names: frozenset[str]) -> bool:
        """Is ``node`` somewhere inside a Call whose callee's last dotted
        component is in ``names``?  (e.g. jax.device_put(jnp.asarray(x)))"""
        for anc in self._ancestors(node):
            if isinstance(anc, ast.Call):
                chain = _attr_chain(anc.func)
                if chain and chain.split(".")[-1] in names:
                    return True
        return False

    def _returned(self, node: ast.AST) -> bool:
        for anc in self._ancestors(node):
            if isinstance(anc, ast.Return):
                return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
        return False

    def _scoped(self, prefixes) -> bool:
        return any(self.relpath.startswith(p) for p in prefixes)

    def _exempt(self, files) -> bool:
        return self.relpath in files

    # -- the rules ---------------------------------------------------------
    def run(self) -> list[Violation]:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                self._check_call(node)
        if self._scoped(HL005_SCOPE):
            self._check_docstrings()
        return self.violations

    def _check_call(self, node: ast.Call) -> None:
        chain = _attr_chain(node.func)
        last = chain.split(".")[-1] if chain else ""

        if (last in RAW_COLLECTIVES and ".lax." in f".{chain}"
                and not self._exempt(HL001_EXEMPT)):
            self._emit("HL001", node,
                       f"raw lax.{last} outside the collective verb layer "
                       "— route through harp_tpu.parallel.collective so "
                       "CommLedger coverage stays total")

        if last == "PRNGKey" and not self._exempt(HL002_EXEMPT):
            self._emit("HL002", node,
                       "jax.random.PRNGKey specializes the program on the "
                       "seed (a fresh compile per seed) "
                       "— use utils.prng.key_bits / split_keys")

        if (last == "asarray" and chain in ("jnp.asarray",
                                            "jax.numpy.asarray")
                and node.args
                and _subtree_mentions_numpy(node.args[0])
                and not self._in_call_to(node, _DEVICE_PUT_FUNCS)):
            self._emit("HL003", node,
                       "jnp.asarray on host numpy data can bake the array "
                       "into the program as a compile-time literal (HTTP "
                       "413 >~50 MB) — use jax.device_put / "
                       "mesh.shard_array")

        if (chain == "jax.jit" and self._scoped(HL004_SCOPE)
                and not self._in_call_to(node, frozenset({"track"}))
                and not self._returned(node)):
            self._emit("HL004", node,
                       "jitted driver callable not wrapped in "
                       "flightrec.track (factories that `return jax.jit("
                       "...)` are exempt: their call sites wrap) — the "
                       "dispatch/readback budgets cannot see this program")

    def _check_docstrings(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.Module, ast.ClassDef,
                                     ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            doc = ast.get_docstring(node, clean=False)
            if not doc or not _PERF_RE.search(doc):
                continue
            missing = []
            if not _DATE_RE.search(doc):
                missing.append("date (YYYY-MM-DD)")
            if not _CHIP_RE.search(doc):
                missing.append("chip (e.g. 1× v5e / CPU)")
            if missing:
                where = (node.body[0] if not isinstance(node, ast.Module)
                         else node.body[0])
                name = getattr(node, "name", "<module>")
                self._emit("HL005", where,
                           f"docstring of {name} carries a perf claim but "
                           f"no {' or '.join(missing)} — perf numbers "
                           "must be re-auditable (CLAUDE.md conventions)")


def lint_source(relpath: str, text: str) -> list[Violation]:
    """Lint one file's source.  ``relpath`` decides rule scoping."""
    try:
        return _Linter(relpath, text).run()
    except SyntaxError as e:
        return [Violation("HL000", relpath, e.lineno or 0,
                          f"unparseable Python: {e.msg}")]


# default scan set: library + drivers + tooling; tests are reference/golden
# code (PRNGKey as the equivalence oracle etc.) and lint their own fixtures
DEFAULT_ROOTS = ("harp_tpu", "scripts", "examples",
                 "chip_smoke.py", "__graft_entry__.py")


def iter_python_files(repo: str, roots=DEFAULT_ROOTS):
    for root in roots:
        p = os.path.join(repo, root)
        if os.path.isfile(p):
            yield root
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.relpath(os.path.join(dirpath, fn),
                                              repo).replace(os.sep, "/")


def lint_paths(repo: str, relpaths=None) -> list[Violation]:
    """Lint ``relpaths`` (default: the whole default scan set)."""
    out: list[Violation] = []
    for rel in (relpaths if relpaths is not None
                else iter_python_files(repo)):
        with open(os.path.join(repo, rel), encoding="utf-8") as fh:
            out.extend(lint_source(rel, fh.read()))
    return out
