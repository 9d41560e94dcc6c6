"""harplint — static analysis for harp-tpu: the traps a CPU sandbox
cannot execute its way into.

Reference parity (SURVEY.md §6): Harp has no static analysis; its
communication discipline is convention only.  This package machine-checks
the conventions (CLAUDE.md traps) in five layers — source AST lints
(:mod:`.astlints`), jaxpr analyzers (:mod:`.jaxpr_checks`), a
no-hardware Mosaic kernel audit (:mod:`.mosaic_audit`), the static
communication-graph auditor (:mod:`.commgraph`, the CommLedger
cross-check + donation audit whose per-program byte sheets ride the
lint JSON row), and the thread-root concurrency auditor
(:mod:`.threadgraph`, whose ownership map also arms the runtime twin
:mod:`harp_tpu.utils.threadguard`) — behind one rule registry
(:mod:`.rules`), one committed
allowlist (``analysis/allowlist.toml``), and one CLI
(``python -m harp_tpu lint``, :mod:`.cli`).

The core currency is :class:`Violation`: every layer emits them, the
allowlist suppresses reviewed exceptions, and the CLI renders the rest as
a human report plus one provenance-stamped ``kind: "lint"`` JSON line
(validated by ``scripts/check_jsonl.py`` invariant 6).
"""

from __future__ import annotations

import dataclasses

from harp_tpu.analysis.rules import RULES, Rule, rule_ids


@dataclasses.dataclass
class Violation:
    """One finding.  ``path`` is repo-relative for source findings, a
    pseudo-path (``kernel:<name>``, ``driver:<name>``) for traced ones —
    allowlist entries match on it either way."""

    rule: str
    path: str
    line: int
    message: str
    source: str = ""     # the offending source line / jaxpr snippet

    def format(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        out = f"{loc}: {self.rule} {self.message}"
        if self.source:
            out += f"\n    {self.source.strip()}"
        return out


__all__ = ["Violation", "Rule", "RULES", "rule_ids"]
