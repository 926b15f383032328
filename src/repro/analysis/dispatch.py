"""Dispatch auditor: prove a jit'd step rides the Pallas fast path.

Walks the closed jaxpr of a step (the same eqn-walking style as
``launch/jaxpr_stats.py``, here with full sub-jaxpr coverage — ``pjit``,
``custom_vjp``/``custom_jvp`` bodies, ``scan``/``while``/``cond`` branches)
and classifies every aggregation/projection into the ROADMAP dispatch tree:

  * ``pallas_call`` eqns, keyed by kernel function name
    (``_spmm_ell_kernel``, ``_gat_ell_kernel``, ``_gmm_kernel``, ...) —
    the fused fast path;
  * eqns inside a ``repro_oracle:<tag>`` named scope (the ref oracles tag
    themselves at trace time) — the XLA fallback branch;
  * eqns inside a ``repro_kernel_vjp:<tag>`` scope — the kernels' own
    custom-VJP backwards, which are gather/scatter XLA programs *by design*
    and must never be read as fallbacks when auditing grad steps;
  * untagged gather/scatter/segment eqns — reported informationally
    (feature lookups, output scatters, packers), never a failure.

``audit_report(fn, *args)`` replaces monkey-patched kernel spies: the claim
"all N relations hit the fused kernel, zero oracle fallbacks" becomes
``audit_report(step, params, batch).assert_fused()``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax

from repro.kernels import pallas_kernel_name

ORACLE_SCOPE = "repro_oracle:"
KERNEL_VJP_SCOPE = "repro_kernel_vjp:"

# Untagged primitives worth surfacing in the informational bucket: the
# building blocks a segment-oracle aggregation would be made of.
_GATHER_SCATTER = ("gather", "scatter", "scatter-add", "scatter_add",
                   "scatter-max", "scatter-min", "take", "segment_sum")

# Cross-device collectives (the shard_map data-parallel step's comm layer).
# Classified *before* any scope check: a psum is communication wherever it
# appears — it must never be mistaken for an oracle fallback.
_COLLECTIVES = ("psum", "all_gather", "all_to_all", "ppermute",
                "reduce_scatter", "pmax", "pmin", "axis_index")


def _scope_tag(name_stack: str, marker: str) -> str:
    """Extract ``<tag>`` from the first ``<marker><tag>`` scope in a stack.

    Name stacks render as ``"a/b/repro_oracle:spmm_csr/c"`` and transforms
    may wrap entries (``transpose(repro_oracle:spmm_csr)``) — take the tag
    up to the next separator or closing paren.
    """
    start = name_stack.index(marker) + len(marker)
    tag = name_stack[start:]
    for sep in ("/", ")"):
        if sep in tag:
            tag = tag[: tag.index(sep)]
    return tag


def _sub_jaxprs(eqn) -> Tuple[List[Tuple[Any, int]], bool]:
    """(jaxpr, multiplier) children of an eqn — full coverage variant."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "scan":
        return [(p["jaxpr"], p["length"])], False
    if name == "while":
        return [(p["body_jaxpr"], 1), (p["cond_jaxpr"], 1)], True
    if name == "cond":
        # audit every branch: any of them can run
        return [(b, 1) for b in p["branches"]], False
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p and p[key] is not None:
            return [(p[key], 1)], False
    return [], False


@dataclasses.dataclass
class DispatchReport:
    """Classified eqn counts of one audited jaxpr (all scan-multiplied)."""
    kernel_launches: Dict[str, int] = dataclasses.field(default_factory=dict)
    oracle_eqns: Dict[str, int] = dataclasses.field(default_factory=dict)
    kernel_vjp_eqns: Dict[str, int] = dataclasses.field(default_factory=dict)
    unattributed_gather_scatter: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    collective_eqns: Dict[str, int] = dataclasses.field(default_factory=dict)
    # pallas_call launches traced with ``interpret`` set: the Pallas
    # interpreter, not a compiled kernel (expected off-TPU, a fault on it)
    interpret_launches: int = 0
    total_eqns: int = 0
    dynamic_trip_warnings: int = 0

    @property
    def oracle_fallbacks(self) -> int:
        """Total eqns attributed to any oracle region (0 == fully fused)."""
        return sum(self.oracle_eqns.values())

    @property
    def total_kernel_launches(self) -> int:
        return sum(self.kernel_launches.values())

    @property
    def total_collectives(self) -> int:
        """Total cross-device collective eqns (psum/all_gather/...)."""
        return sum(self.collective_eqns.values())

    def assert_fused(self, *, expect_kernels: Tuple[str, ...] = (),
                     min_launches: int = 1,
                     expect_collectives: Dict[str, int] = None
                     ) -> "DispatchReport":
        """Fail unless the step is fully on the fast path.

        Asserts zero oracle-region eqns, at least ``min_launches``
        ``pallas_call`` eqns overall, and (when given) at least one launch
        of each kernel in ``expect_kernels``. ``expect_collectives`` pins
        the *exact* per-primitive collective counts (golden audit of a
        sharded step: e.g. ``{"psum": 1}`` for the single fused gradient
        all-reduce; primitives absent from the dict must not appear).
        Returns self for chaining.
        """
        if self.oracle_fallbacks:
            raise AssertionError(
                f"oracle fallback detected: {self.oracle_eqns} "
                f"(kernel launches seen: {self.kernel_launches or 'none'})")
        if self.total_kernel_launches < min_launches:
            raise AssertionError(
                f"expected >= {min_launches} pallas_call launches, saw "
                f"{self.total_kernel_launches} ({self.kernel_launches})")
        for k in expect_kernels:
            if self.kernel_launches.get(k, 0) < 1:
                raise AssertionError(
                    f"expected kernel {k!r} was never launched; saw "
                    f"{self.kernel_launches}")
        if expect_collectives is not None and \
                dict(self.collective_eqns) != dict(expect_collectives):
            raise AssertionError(
                f"collective eqns {dict(self.collective_eqns)} != expected "
                f"{dict(expect_collectives)}")
        return self

    def summary(self) -> Dict[str, Any]:
        """JSON-ready summary (the benchmark audit cell's payload)."""
        return {
            "kernel_launches": dict(self.kernel_launches),
            "interpret_launches": self.interpret_launches,
            "oracle_fallback_eqns": dict(self.oracle_eqns),
            "oracle_fallbacks": self.oracle_fallbacks,
            "kernel_vjp_eqns": dict(self.kernel_vjp_eqns),
            "unattributed_gather_scatter":
                dict(self.unattributed_gather_scatter),
            "collective_eqns": dict(self.collective_eqns),
            "total_collectives": self.total_collectives,
            "total_eqns": self.total_eqns,
        }


def audit_jaxpr(jaxpr, mult: int = 1,
                report: DispatchReport = None) -> DispatchReport:
    """Classify every eqn of a (closed) jaxpr into the dispatch tree."""
    if report is None:
        report = DispatchReport()
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            kernel = pallas_kernel_name(eqn)
            report.kernel_launches[kernel] = report.kernel_launches.get(
                kernel, 0) + mult
            if eqn.params.get("interpret"):
                report.interpret_launches += mult
            report.total_eqns += mult
            continue
        if name in _COLLECTIVES:
            report.collective_eqns[name] = report.collective_eqns.get(
                name, 0) + mult
            report.total_eqns += mult
            continue
        subs, is_while = _sub_jaxprs(eqn)
        if subs:
            if is_while:
                report.dynamic_trip_warnings += mult
            for sub, length in subs:
                audit_jaxpr(sub, mult * length, report)
            continue
        report.total_eqns += mult
        stack = str(getattr(eqn.source_info, "name_stack", "") or "")
        if KERNEL_VJP_SCOPE in stack:
            tag = _scope_tag(stack, KERNEL_VJP_SCOPE)
            report.kernel_vjp_eqns[tag] = report.kernel_vjp_eqns.get(
                tag, 0) + mult
        elif ORACLE_SCOPE in stack:
            tag = _scope_tag(stack, ORACLE_SCOPE)
            report.oracle_eqns[tag] = report.oracle_eqns.get(tag, 0) + mult
        elif name in _GATHER_SCATTER:
            report.unattributed_gather_scatter[name] = \
                report.unattributed_gather_scatter.get(name, 0) + mult
    return report


def audit_report(fn, *args, **kwargs) -> DispatchReport:
    """Trace ``fn(*args, **kwargs)`` abstractly and audit its dispatch.

    ``fn`` may be a plain callable or an already-``jax.jit``-ed one; the
    trace is abstract (no compilation, no execution), so auditing a
    ``value_and_grad`` train step is cheap.
    """
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return audit_jaxpr(jaxpr)
