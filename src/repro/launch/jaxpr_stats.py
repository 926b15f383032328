"""Exact, scan-aware FLOP / HBM-byte accounting from the step jaxpr.

XLA:CPU's ``compiled.cost_analysis()`` counts ``while``/scan bodies once, so
on this container it under-reports the 40-layer models by orders of
magnitude. The jaxpr is the pre-partitioning *global* program with explicit
scan lengths, so walking it yields exact global FLOPs — the numerator the
roofline needs (differentiation is a trace-time transform, so the walked
jaxpr already includes backward + remat recompute).

Byte accounting uses a fusion-aware HBM-traffic model: only ops whose
operands/results must transit HBM on TPU are charged — dots/convs
(operands+outputs), gathers/scatters (output+updates), reduces (operands) —
while elementwise chains are treated as fused into their producers. This is
the standard postfusion traffic approximation (cf. roofline practice in
MaxText/JAX-toolbox perf notes).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import numpy as np
from jax import core

from repro.kernels import pallas_kernel_name

FLOP_REPORT_KEYS = ("dot_flops", "conv_flops", "elementwise_flops",
                    "pallas_flops", "total_flops", "major_bytes",
                    "collective_bytes", "while_warning")

# Cross-device collectives: per-device wire-traffic model (ring/bidirectional
# approximations — what the compressed-vs-raw all-reduce comparison needs,
# not a topology simulator). psum moves ~2x its operand bytes on a ring;
# all_gather receives (out - in) bytes; reduce_scatter/all_to_all/ppermute
# move their operand bytes once.
_COLLECTIVE_BYTES = {
    "psum": lambda inb, outb: 2 * inb,
    "all_gather": lambda inb, outb: max(outb - inb, 0),
    "reduce_scatter": lambda inb, outb: inb,
    "all_to_all": lambda inb, outb: inb,
    "ppermute": lambda inb, outb: inb,
    "axis_index": lambda inb, outb: 0,
    "pmax": lambda inb, outb: 2 * inb,
    "pmin": lambda inb, outb: 2 * inb,
}


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> int:
    dn = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dn
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = 1
    for d in lb:
        batch *= lhs.shape[d]
    k = 1
    for d in lc:
        k *= lhs.shape[d]
    m = 1
    for i, s in enumerate(lhs.shape):
        if i not in lc and i not in lb:
            m *= s
    n = 1
    for i, s in enumerate(rhs.shape):
        if i not in rc and i not in rb:
            n *= s
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    # flops = 2 * out_elements * kernel_spatial * (C_in / groups); the rhs
    # already carries C_in/groups on its input-feature dim, so it's simply
    # 2 * out_elems * prod(rhs_nonoutput_dims).
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_feat_dim = dn.rhs_spec[0] if hasattr(dn, "rhs_spec") else 0
    kernel_elems = int(np.prod(rhs.shape)) // rhs.shape[out_feat_dim]
    return 2 * int(np.prod(out.shape)) * kernel_elems


def _sub_jaxprs(eqn):
    """(jaxpr, multiplier) children of an eqn, handling scan/cond/etc."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "scan":
        return [(p["jaxpr"], p["length"])], False
    if name == "while":
        return [(p["body_jaxpr"], 1), (p["cond_jaxpr"], 1)], True
    if name == "cond":
        return [(b, 1) for b in p["branches"][:1]], False  # branch max ~ first
    # "fun_jaxpr" is the custom_vjp body: without it the kernel wrappers'
    # forward work would be invisible to the accounting.
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p and p[key] is not None:
            return [(p[key], 1)], False
    return [], False


# ------------------------------------------------- pallas_call cost models
# The kernel body is opaque to XLA (and to the generic eqn walk), so each
# kernel gets an analytic cost keyed off its function name — the same names
# analysis.dispatch audits. Costs are (flops, hbm_bytes) per launch, read
# off the eqn's operand/result avals.


def _avals(eqn):
    ins = [v.aval for v in eqn.invars if hasattr(v.aval, "shape")]
    outs = [v.aval for v in eqn.outvars if hasattr(v.aval, "shape")]
    return ins, outs


def _ell_table(ins):
    """The (R, K) int32 neighbor table aval (first 2D integer operand)."""
    for a in ins:
        if len(a.shape) == 2 and np.issubdtype(a.dtype, np.integer):
            return a
    return None


def _spmm_ell_cost(eqn):
    ins, outs = _avals(eqn)
    table, out = _ell_table(ins), outs[0]
    r, k = table.shape
    feat = out.shape[-1]
    weighted = any(len(a.shape) == 2 and a.shape == (r, k)
                   and not np.issubdtype(a.dtype, np.integer) for a in ins)
    flops = (3 if weighted else 2) * r * k * feat  # 2*nnz*F (+w mul)
    nbytes = (r * k * 4  # prefetched table
              + r * k * feat * out.dtype.itemsize  # neighbor-row gather DMAs
              + _nbytes(out))
    return flops, nbytes


def _gat_ell_cost(eqn):
    ins, outs = _avals(eqn)
    table, out = _ell_table(ins), outs[0]
    r, k = table.shape
    # operands: adst (R, H) identifies H; out is (R, H*F)
    heads = next((a.shape[1] for a in ins
                  if len(a.shape) == 2 and a.shape[0] == r
                  and not np.issubdtype(a.dtype, np.integer)), 1)
    hf = out.shape[-1]
    # softmax (exp/max/sum ~ 8 ops per (row, slot, head)) + accumulate
    flops = r * k * (2 * hf + 8 * heads)
    nbytes = (r * k * 4 + r * k * hf * out.dtype.itemsize
              + r * k * heads * 4 + _nbytes(out))
    return flops, nbytes


def _attn_ell_cost(eqn):
    """Carry-mode typed-attention launch: outs = (acc (R,H*F), m, l (H,R,1))."""
    ins, outs = _avals(eqn)
    table = _ell_table(ins)
    r, k = table.shape
    acc, m = outs[0], outs[1]
    heads = m.shape[0]
    hf = acc.shape[-1]
    # adst is the (R, H*LD) float operand row-aligned with the table
    adst = next((a for a in ins
                 if len(a.shape) == 2 and a.shape[0] == r and a.shape[1] != k
                 and not np.issubdtype(a.dtype, np.integer)), None)
    ld = (adst.shape[1] // max(heads, 1)) if adst is not None else 1
    # per (row, slot): LD-wide dot per head + online softmax + accumulate
    flops = r * k * (2 * heads * ld + 8 * heads + 2 * hf)
    nbytes = (r * k * 4 + r * k * hf * acc.dtype.itemsize
              + r * k * heads * ld * 4 + sum(_nbytes(o) for o in outs))
    return flops, nbytes


def _gmm_cost(eqn):
    ins, outs = _avals(eqn)
    x = next(a for a in ins if len(a.shape) == 2
             and not np.issubdtype(a.dtype, np.integer))
    w = next(a for a in ins if len(a.shape) == 3)
    m, k = x.shape
    n = w.shape[2]
    flops = 2 * m * k * n  # sum over groups of 2*m_g*k*n; m = sum m_g
    nbytes = _nbytes(x) + _nbytes(w) + sum(_nbytes(o) for o in outs)
    return flops, nbytes


def _segment_softmax_cost(eqn):
    ins, outs = _avals(eqn)
    elems = max((int(np.prod(a.shape)) for a in ins), default=0)
    nbytes = sum(_nbytes(a) for a in ins) + sum(_nbytes(o) for o in outs)
    return 5 * elems, nbytes


def _flash_cost(eqn):
    ins, outs = _avals(eqn)
    floats = [a for a in ins if not np.issubdtype(a.dtype, np.integer)
              and len(a.shape) >= 3]
    q, kv = floats[0], floats[1]
    lq, d = q.shape[-2], q.shape[-1]
    lkv = kv.shape[-2]
    batch = int(np.prod(q.shape[:-2]))
    flops = 4 * batch * lq * lkv * d  # qk^T + softmax*V
    nbytes = sum(_nbytes(a) for a in floats) + sum(_nbytes(o) for o in outs)
    return flops, nbytes


_PALLAS_COSTS = {
    "_spmm_ell_kernel": _spmm_ell_cost,
    "_gat_ell_kernel": _gat_ell_cost,
    "_attn_ell_kernel": _attn_ell_cost,
    "_gmm_kernel": _gmm_cost,
    "_segment_softmax_kernel": _segment_softmax_cost,
    "_flash_kernel": _flash_cost,
}


def _pallas_cost(eqn):
    """(flops, bytes) of one pallas_call eqn, keyed off the kernel name."""
    fn = _PALLAS_COSTS.get(pallas_kernel_name(eqn))
    if fn is not None:
        try:
            return fn(eqn)
        except (StopIteration, IndexError, AttributeError):
            pass  # shape layout drifted: fall through to the generic model
    ins, outs = _avals(eqn)
    elems = sum(int(np.prod(a.shape)) for a in outs)
    nbytes = sum(_nbytes(a) for a in ins) + sum(_nbytes(o) for o in outs)
    return elems, nbytes


def analyze_jaxpr(jaxpr, mult: int = 1, acc: Dict[str, float] = None
                  ) -> Dict[str, float]:
    if acc is None:
        acc = {k: 0 for k in FLOP_REPORT_KEYS}
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            f, nb = _pallas_cost(eqn)
            acc["pallas_flops"] += mult * f
            acc["total_flops"] += mult * f
            acc["major_bytes"] += mult * nb
            continue
        if name in _COLLECTIVE_BYTES:
            inb = sum(_nbytes(v.aval) for v in eqn.invars
                      if hasattr(v.aval, "shape"))
            outb = sum(_nbytes(v.aval) for v in eqn.outvars
                       if hasattr(v.aval, "shape"))
            acc["collective_bytes"] += mult * _COLLECTIVE_BYTES[name](
                inb, outb)
            continue
        subs, is_while = _sub_jaxprs(eqn)
        if subs:
            if is_while:
                acc["while_warning"] += mult  # dynamic trip: counted once
            for sub, length in subs:
                analyze_jaxpr(sub, mult * length, acc)
            continue
        if name == "dot_general":
            f = _dot_flops(eqn)
            acc["dot_flops"] += mult * f
            acc["total_flops"] += mult * f
            acc["major_bytes"] += mult * (
                sum(_nbytes(v.aval) for v in eqn.invars)
                + sum(_nbytes(v.aval) for v in eqn.outvars))
        elif name == "conv_general_dilated":
            f = _conv_flops(eqn)
            acc["conv_flops"] += mult * f
            acc["total_flops"] += mult * f
            acc["major_bytes"] += mult * (
                sum(_nbytes(v.aval) for v in eqn.invars)
                + sum(_nbytes(v.aval) for v in eqn.outvars))
        elif name in ("gather", "scatter", "scatter-add", "scatter_add",
                      "dynamic_slice", "dynamic_update_slice", "take",
                      "argsort", "sort"):
            nb = sum(_nbytes(v.aval) for v in eqn.outvars)
            if name in ("sort", "argsort"):
                n = max(int(np.prod(eqn.invars[0].aval.shape)), 1)
                acc["elementwise_flops"] += mult * n * max(
                    int(math.log2(n)), 1)
                acc["total_flops"] += mult * n * max(int(math.log2(n)), 1)
            acc["major_bytes"] += mult * nb
        elif name.startswith("reduce_") or name == "reduce":
            nb = sum(_nbytes(v.aval) for v in eqn.invars)
            f = sum(int(np.prod(v.aval.shape)) for v in eqn.invars)
            acc["elementwise_flops"] += mult * f
            acc["total_flops"] += mult * f
            acc["major_bytes"] += mult * nb
        else:
            f = sum(int(np.prod(v.aval.shape)) for v in eqn.outvars
                    if hasattr(v.aval, "shape"))
            acc["elementwise_flops"] += mult * f
            acc["total_flops"] += mult * f
    return acc


def step_stats(fn, *abstract_args) -> Dict[str, float]:
    """Trace ``fn`` abstractly and return global FLOP/byte stats."""
    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    return analyze_jaxpr(jaxpr)
