"""Committed allowlist — intentional lint exceptions, explicit and reviewed.

``analysis/allowlist.toml`` (next to this module) holds one ``[[allow]]``
table per exception:

.. code-block:: toml

    [[allow]]
    rule = "HL002"
    path = "scripts/drive_check.py"
    match = "jax.random.PRNGKey(_fr_seed)"   # optional line-content anchor
    reason = "golden reference: the trap is the oracle here"

``rule`` + ``path`` are required and must match the violation exactly;
``match`` (optional) additionally requires the flagged source line to
contain the substring — entries stay pinned to the code they excuse even
as line numbers drift.  ``reason`` is required: an allowlist entry
without a justification is itself a violation of the review contract, so
loading fails loudly.  Entries that match nothing are reported as stale
by the CLI (``--prune`` lists them) so the file cannot silently rot.
"""

from __future__ import annotations

import os
import tomllib as _toml

from harp_tpu.analysis import Violation

DEFAULT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "allowlist.toml")


class AllowlistError(ValueError):
    """Malformed allowlist file (missing rule/path/reason)."""


def load(path: str | None = None) -> list[dict]:
    path = path or DEFAULT_PATH
    if not os.path.exists(path):
        return []
    with open(path, "rb") as fh:
        data = _toml.load(fh)
    entries = data.get("allow", [])
    for i, e in enumerate(entries):
        for field in ("rule", "path", "reason"):
            if not e.get(field):
                raise AllowlistError(
                    f"{os.path.basename(path)}: [[allow]] entry #{i + 1} "
                    f"missing required field {field!r} — every exception "
                    "needs a rule, a path, and a one-line justification")
        e.setdefault("_hits", 0)
    return entries


def matches(entry: dict, v: Violation) -> bool:
    if entry["rule"] != v.rule or entry["path"] != v.path:
        return False
    m = entry.get("match")
    return m is None or m in (v.source or "")


def apply(violations: list[Violation], entries: list[dict]
          ) -> tuple[list[Violation], list[Violation], list[dict]]:
    """(kept, suppressed, stale_entries) — entries count their hits so
    stale ones (matched nothing this run) can be reported."""
    kept: list[Violation] = []
    suppressed: list[Violation] = []
    for v in violations:
        hit = None
        for e in entries:
            if matches(e, v):
                hit = e
                break
        if hit is None:
            kept.append(v)
        else:
            hit["_hits"] += 1
            suppressed.append(v)
    stale = [e for e in entries if e["_hits"] == 0]
    return kept, suppressed, stale
