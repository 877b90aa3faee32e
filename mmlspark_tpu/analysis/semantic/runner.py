"""Semantic-tier runner: load contracts, lower, check, report.

This is the only module in the analyzer that touches jax — and it does
so lazily, behind the same degradation discipline as the lowering
layer. On a machine where the backend has not initialized yet it pins
the 8-virtual-device CPU configuration tests use (the collectives in
shard_map'd contracts only survive into the optimized module when a
real multi-device mesh lowers them — one device would make the
collective-budget checker vacuous).
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

from ..core import Finding, Module
from .checkers import ALL_CHECKERS, SEMANTIC_RULES
from .contracts import HotPathContract
from .lowering import lower_case
from .registry import load_contracts

ANALYSIS_DEVICE_COUNT = 8   # the tier-1 virtual CPU mesh (tests/conftest.py)


class SemanticReport:
    """Findings plus the per-contract evidence tests pin against."""

    def __init__(self):
        self.findings: List[Finding] = []      # suppression-filtered
        self.errors: List[Finding] = []        # contract-import (exit 2)
        self.contracts: List[str] = []
        self.stats: dict = {}                  # contract -> evidence

    @property
    def all_findings(self) -> List[Finding]:
        return self.errors + self.findings


def _ensure_devices() -> None:
    """Pin the canonical analysis backend BEFORE it initializes: CPU
    with 8 virtual devices. A backend someone else already initialized
    (pytest's conftest, a trainer in the same process) is left alone —
    contracts adapt to whatever mesh exists and budgets are maxima."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    if jax._src.xla_bridge._backends:
        return     # initialized; reconfiguring now would fail
    jax.config.update("jax_num_cpu_devices", ANALYSIS_DEVICE_COUNT)


def _suppression_module(path: str, root: str) -> Optional[Module]:
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    except OSError:
        return None
    rel = os.path.relpath(path, root).replace(os.sep, "/")
    return Module(path, rel, source)


def run_semantic(root: Optional[str] = None,
                 entrypoints: Optional[Sequence[Tuple[str, str]]] = None,
                 rules: Optional[Sequence[str]] = None) -> SemanticReport:
    """Run the semantic tier. `rules` filters to a subset of
    SEMANTIC_RULES ids (contract-import errors always report);
    `entrypoints` overrides the shipped registry (fixture tests)."""
    root = os.path.abspath(root or os.getcwd())
    wanted = set(rules) if rules is not None else set(SEMANTIC_RULES)
    report = SemanticReport()
    _ensure_devices()

    contracts, errors = load_contracts(entrypoints)
    for f in errors:
        f.path = os.path.relpath(f.path, root).replace(os.sep, "/")
    report.errors.extend(errors)

    modules: dict = {}
    for contract in contracts:
        rel = os.path.relpath(contract.path, root).replace(os.sep, "/")
        report.contracts.append(contract.name)
        try:
            cases = list(contract.cases())
        except Exception as e:  # noqa: BLE001 - a builder that cannot even
            # construct its cases leaves the path unanalyzed: gate like a
            # moved entrypoint, not like a degraded field
            report.errors.append(Finding(
                "semantic.contract-import", rel, contract.line, 0,
                f"contract '{contract.name}' case builder raised "
                f"{type(e).__name__}: {e}", tier="semantic"))
            continue
        lowered = [lower_case(c) for c in cases]
        report.stats[contract.name] = {
            "path": rel,
            "cases": [lc.name for lc in lowered],
            "fingerprints": {lc.name: lc.fingerprint for lc in lowered},
            "fingerprint_basis": {lc.name: lc.fingerprint_basis
                                  for lc in lowered},
            "distinct_executables": len(
                {lc.fingerprint for lc in lowered
                 if lc.fingerprint is not None}),
            "donated_args": {lc.name: lc.donated_args for lc in lowered},
            "collectives": {lc.name: lc.collectives for lc in lowered},
            "degraded": {lc.name: dict(lc.degraded) for lc in lowered
                         if lc.degraded},
        }
        if contract.path not in modules:
            modules[contract.path] = _suppression_module(contract.path, root)
        module = modules[contract.path]
        for checker in ALL_CHECKERS:
            for f in checker(contract, rel, lowered):
                if f.rule not in wanted:
                    continue
                if module is not None and module.suppressed(f):
                    continue
                report.findings.append(f)

    try:  # observability of the analyzer itself; never fails the run
        from ...reliability.metrics import reliability_metrics
        from ...telemetry import names as tnames
        reliability_metrics.set_gauge(
            tnames.ANALYSIS_SEMANTIC_CONTRACTS, float(len(contracts)))
        reliability_metrics.set_gauge(
            tnames.ANALYSIS_SEMANTIC_FINDINGS,
            float(len(report.all_findings)))
    except Exception:  # noqa: BLE001 - telemetry optional under the CLI
        pass
    return report
