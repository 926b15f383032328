"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device planes (``/device:TPU:<n>``) carry one event per executed
operation on their ``XLA Ops`` line; the host planes carry the benchmark's
own spans (``jax.profiler.TraceAnnotation``), on the same clock. Within the
window span:

  * busy time: the length of the union of a device's operation intervals;
  * per-operation time: the summed durations of a name's events, where an
    operation's name is its HLO instruction's (``fusion.25``, with its
    output type and fusion kind), and a custom call's is the kernel it
    launches, whatever its instruction number (``_spmm_ell_kernel``);
  * idle gaps: the stretches of the window in which device 0 ran nothing,
    each labelled by the host span that covers its middle.

Device numbers are averaged over the devices in the trace.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted union of half-open intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi) not covered by the (merged) ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(hlo: str) -> str:
    """A device operation's name from the HLO text the TPU trace gives it
    (``%name = type op(...), kind=...``): a custom call (a Pallas launch)
    by its kernel's name, anything else by its instruction name, output
    type and fusion kind."""
    lhs, sep, rhs = hlo.partition(" = ")
    name = lhs.strip().lstrip("%")
    if not sep:
        return name
    if " custom-call(" in rhs:
        return re.sub(r"\.\d+$", "", name)
    out_type = rhs.split("{", 1)[0].split(" ", 1)[0].lstrip("(")
    kind = re.search(r"kind=(k\w+)", rhs)
    return " ".join([name, out_type] + ([kind.group(1)] if kind else []))


def read_events(path: str, span_names: Sequence[str]):
    """(host spans, device ops): ``{name: [(start, end)]}`` on the host and
    ``{device: [(name, start, end)]}``, in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    wanted = set(span_names)
    spans: Dict[str, List[Interval]] = {n: [] for n in span_names}
    devices: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((op_name(ev.name), s, s + ev.duration_ns * 1e-9))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name in wanted:
                        s = ev.start_ns * 1e-9
                        spans[ev.name].append(
                            (s, s + ev.duration_ns * 1e-9))
    return spans, devices


def reduce(path: str, window_span: str, span_names: Sequence[str],
           top: int = 10) -> Dict:
    """The reduced trace of the (single) ``window_span`` window."""
    spans, devices = read_events(path, list(span_names) + [window_span])
    if len(spans[window_span]) != 1:
        raise ValueError(f"trace holds {len(spans[window_span])} "
                         f"{window_span!r} spans, expected 1")
    if not devices:
        raise ValueError("trace holds no device operations")
    lo, hi = spans[window_span][0]
    busy_s, ops_s = [], {}
    first_busy = None
    for dev in sorted(devices):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[dev]
               if e > lo and s < hi]
        busy = merge((s, e) for _, s, e in ops)
        if first_busy is None:
            first_busy = busy
        busy_s.append(sum(e - s for s, e in busy))
        for n, s, e in ops:
            ops_s[n] = ops_s.get(n, 0.0) + (e - s)
    ndev = len(devices)
    ops_s = {n: t / ndev for n, t in ops_s.items()}
    host = [(n, s, e) for n in span_names for s, e in spans[n]]
    labelled = []
    for s, e in gaps(first_busy, lo, hi):
        mid = 0.5 * (s + e)
        label = next((n for n, hs, he in host if hs <= mid < he), "host")
        labelled.append((label, e - s))
    labelled.sort(key=lambda g: -g[1])
    return {
        "window_s": hi - lo,
        "devices": ndev,
        "busy_s": sum(busy_s) / ndev,
        "ops_s": ops_s,
        "device_ops": sorted(ops_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": labelled[:top],
    }
