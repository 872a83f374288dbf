"""KL001 — unledgered host<->device crossings.

PR-7's TransferLedger is the bytes-budget instrument: the benchmark's
transfer metrics and the per-window movement report are only
honest if EVERY ``jax.device_get`` / ``jax.device_put`` /
``.block_until_ready()`` site is metered. A crossing added outside the
ledger silently disappears from ``khipu_device_transfer_*`` and the
bytes/block figures — the budget then lies exactly when it is
supposed to catch a regression.

A crossing counts as metered when it is lexically inside a
``with *.transfer(...)`` timing context, or when the enclosing function
also calls ``*LEDGER*.record(...)`` (the one-shot form used where the
upload is async and the timing context would double-count — see
storage/device_mirror.py mirror.init).

The rule also validates the SITE STRING of every ledger call against
``profiler.KNOWN_SITES``: a misspelled site is metered in the totals
but silently forks a new series in ``khipu_device_transfer_*`` and
drops out of its COLLECT_CLASSES stream — the window report then
under-attributes exactly the bytes the site was added to explain.
"""

from __future__ import annotations

import ast
from typing import Iterator, Set

from khipu_tpu.analysis.core import (
    SEVERITY_ERROR,
    Finding,
    Module,
    enclosing_function,
    in_with_transfer,
    parent,
)

RULE_ID = "KL001"

_EXEMPT_SUFFIXES = (
    "observability/profiler.py",  # the instrument itself
)

_CROSSING_ATTRS = {"device_get", "device_put"}


def _jax_aliases(tree: ast.Module) -> tuple[Set[str], Set[str]]:
    """(module aliases for jax, names from-imported out of jax)."""
    mods: Set[str] = set()
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax" or a.name.startswith("jax."):
                    mods.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] == "jax":
                for a in node.names:
                    if a.name in _CROSSING_ATTRS:
                        names.add(a.asname or a.name)
    return mods, names


def _crossing_name(call: ast.Call, mods: Set[str],
                   names: Set[str]) -> str:
    f = call.func
    if isinstance(f, ast.Attribute):
        if (
            f.attr in _CROSSING_ATTRS
            and isinstance(f.value, ast.Name)
            and f.value.id in mods
        ):
            return f"jax.{f.attr}"
        if f.attr == "block_until_ready":
            return ".block_until_ready"
    elif isinstance(f, ast.Name) and f.id in names:
        return f.id
    return ""


def _known_sites() -> Set[str]:
    """The runtime site registry — imported lazily so the analyzer can
    still scan trees where observability fails to import."""
    try:
        from khipu_tpu.observability.profiler import KNOWN_SITES

        return set(KNOWN_SITES)
    except Exception:  # pragma: no cover - defensive
        return set()


def _ledger_site_arg(call: ast.Call) -> str | None:
    """The literal site string of a ``*LEDGER*.transfer(...)`` /
    ``*LEDGER*.record(...)`` call, or None when the call is not a
    ledger call or the site is not a string literal (dynamic sites are
    out of the rule's reach)."""
    f = call.func
    if not isinstance(f, ast.Attribute) or f.attr not in (
        "transfer", "record"
    ):
        return None
    if "ledger" not in ast.unparse(f.value).lower():
        return None
    if not call.args:
        return None
    a0 = call.args[0]
    if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
        return a0.value
    return None


def _function_records_to_ledger(node: ast.AST) -> bool:
    fn = parent(node)
    while fn is not None and not isinstance(
        fn, (ast.FunctionDef, ast.AsyncFunctionDef)
    ):
        fn = parent(fn)
    if fn is None:
        return False
    for sub in ast.walk(fn):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr == "record"
            and "ledger" in ast.unparse(sub.func.value).lower()
        ):
            return True
    return False


class Rule:
    id = RULE_ID
    severity = SEVERITY_ERROR
    description = (
        "host<->device crossing not metered by the TransferLedger"
    )

    def check_module(self, mod: Module) -> Iterator[Finding]:
        if mod.path.endswith(_EXEMPT_SUFFIXES):
            return
        known = _known_sites()
        mods, names = _jax_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            site = _ledger_site_arg(node)
            if site is not None and known and site not in known:
                yield Finding(
                    rule=self.id,
                    severity=self.severity,
                    path=mod.path,
                    line=node.lineno,
                    message=(
                        f"unknown TransferLedger site {site!r} — not "
                        "in profiler.KNOWN_SITES (a misspelled site "
                        "forks its own metrics series and drops out "
                        "of the window report's class breakdown)"
                    ),
                    context=enclosing_function(node),
                )
                continue
            name = _crossing_name(node, mods, names)
            if not name:
                continue
            if in_with_transfer(node):
                continue
            if _function_records_to_ledger(node):
                continue
            yield Finding(
                rule=self.id,
                severity=self.severity,
                path=mod.path,
                line=node.lineno,
                message=(
                    f"unledgered device crossing `{name}` — wrap in "
                    "`with LEDGER.transfer(site, direction, nbytes):` "
                    "or account it via `LEDGER.record(...)` in the "
                    "same function"
                ),
                context=enclosing_function(node),
            )
