"""Abstract lowering of contract cases, with per-field degradation.

Each `Case` is taken through the AOT chain — `jax.jit(fn, **kw)` ->
`.lower(*args)` -> `.compile()` — and every derived view (jaxpr,
StableHLO text, optimized-HLO text, donation aliases, executable
fingerprint, output avals) is computed independently under the
`executable_analysis` never-raise contract: a backend that cannot
produce one view degrades THAT FIELD (recorded in `degraded` with the
reason) and the checkers that need it go quiet, while everything else
stays live. On the tier-1 CPU backend the chain completes end to end,
so executable-identity and collective-budget run non-vacuously there.
"""
from __future__ import annotations

from typing import Optional

from .contracts import Case

# jaxpr primitives that cross the device->host boundary mid-program;
# anything here not in the contract's allowlist is an unintended host
# sync inside the hot loop
HOST_SYNC_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback", "outside_call", "outfeed", "infeed",
})
# `jax.debug.print` has a primitive of its own in this JAX; it is reported
# under the name contracts allowlist it by, the debug callback it was
_REPORTED_AS = {"debug_print": "debug_callback"}


class LoweredCase:
    """Everything the checkers read about one lowered case."""

    def __init__(self, case: Case):
        self.case = case
        self.name = case.name
        self.group = case.group
        self.degraded: dict = {}       # field -> reason it is unavailable
        self.jaxpr = None
        self.lowered_text: Optional[str] = None
        self.compiled_text: Optional[str] = None
        self.fingerprint: Optional[str] = None
        self.fingerprint_basis: Optional[str] = None  # compiled | stablehlo
        self.donated_args: Optional[tuple] = None   # user-arg indices
        self.out_avals: Optional[list] = None       # flat ShapeDtypeStructs
        self.collectives: Optional[dict] = None

    def _degrade(self, field: str, err: BaseException) -> None:
        self.degraded[field] = f"{type(err).__name__}: {err}"


def _arg_leaf_spans(args) -> list:
    """Flattened-parameter index range per user arg: jit flattens the
    positional args in order, so leaf param `i` belongs to the arg whose
    span contains it."""
    import jax

    spans, lo = [], 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        spans.append((lo, lo + n))
        lo += n
    return spans


def _params_to_args(param_ids, spans) -> tuple:
    out = set()
    for p in param_ids:
        for i, (lo, hi) in enumerate(spans):
            if lo <= p < hi:
                out.add(i)
                break
    return tuple(sorted(out))


def lower_case(case: Case) -> LoweredCase:
    """Lower one case; never raises (a totally un-lowerable case comes
    back with every field degraded)."""
    import jax

    from ...telemetry import perf

    lc = LoweredCase(case)
    try:
        jitted = jax.jit(case.fn, **case.jit_kwargs)
        lowered = jitted.lower(*case.args)
    except Exception as e:  # noqa: BLE001 - degrade, never raise
        for field in ("lowered_text", "compiled_text", "fingerprint",
                      "donated_args", "jaxpr", "out_avals", "collectives"):
            lc._degrade(field, e)
        return lc

    try:
        lc.lowered_text = lowered.as_text()
    except Exception as e:  # noqa: BLE001
        lc._degrade("lowered_text", e)

    compiled_text = None
    try:
        compiled_text = lowered.compile().as_text()
        lc.compiled_text = compiled_text
    except Exception as e:  # noqa: BLE001
        lc._degrade("compiled_text", e)

    # fingerprint prefers the optimized module (it is what executes —
    # the PR-4 two-executables bug is only visible post-GSPMD); the
    # pre-optimization StableHLO is the degraded stand-in
    basis = compiled_text or lc.lowered_text
    if basis is not None:
        lc.fingerprint = perf.hlo_fingerprint(basis)
        lc.fingerprint_basis = "compiled" if compiled_text else "stablehlo"
        if compiled_text is None:
            lc.degraded.setdefault(
                "fingerprint", "compiled text unavailable; "
                "fingerprinting pre-optimization StableHLO")
    else:
        lc.degraded.setdefault("fingerprint", "no module text")

    if compiled_text is not None:
        try:
            params = perf.donation_aliases(compiled_text)
            lc.donated_args = _params_to_args(
                params, _arg_leaf_spans(case.args))
        except Exception as e:  # noqa: BLE001
            lc._degrade("donated_args", e)
        try:
            lc.collectives = perf.collective_traffic(compiled_text)
        except Exception as e:  # noqa: BLE001
            lc._degrade("collectives", e)
    else:
        lc._degrade("donated_args", ValueError("no compiled text"))
        lc._degrade("collectives", ValueError("no compiled text"))

    try:
        lc.jaxpr = jax.make_jaxpr(case.fn)(*case.args)
    except Exception as e:  # noqa: BLE001
        lc._degrade("jaxpr", e)

    try:
        out = jax.eval_shape(case.fn, *case.args)
        lc.out_avals = list(jax.tree_util.tree_leaves(out))
    except Exception as e:  # noqa: BLE001
        lc._degrade("out_avals", e)
    return lc


def host_sync_primitives(jaxpr) -> list:
    """All HOST_SYNC_PRIMITIVES reachable from a (closed) jaxpr,
    including inside nested sub-jaxprs (scan/while/cond/pjit bodies)."""
    hits, seen = [], set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        inner = getattr(jx, "jaxpr", jx)   # ClosedJaxpr -> Jaxpr
        for eqn in getattr(inner, "eqns", ()):
            name = eqn.primitive.name
            if name in HOST_SYNC_PRIMITIVES:
                hits.append(_REPORTED_AS.get(name, name))
            elif "callback" in name:   # future-proof: new callback prims
                hits.append(name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        walk(sub)

    walk(jaxpr)
    return hits


def aval_bytes(aval) -> int:
    import numpy as np

    try:
        return int(np.prod(aval.shape, dtype=np.int64)
                   * np.dtype(aval.dtype).itemsize)
    except Exception:  # noqa: BLE001 - opaque avals count as zero
        return 0
