"""The named scope each device operation of a TPU trace ran under, and the
device time of the kernels' custom-VJP backwards.

The program tags the backward of each kernel's custom VJP with a
``jax.named_scope("repro_kernel_vjp:<tag>")`` (``kernels/*/ops.py``). XLA
keeps the scope in each HLO instruction's ``metadata={op_name=...}``, a
fusion taking its root's, and the TPU trace keeps that op_name with each
instruction's event metadata on the device plane, as the ``tf_op`` stat.
``jax.profiler.ProfileData`` reads events but not event metadata, so
:func:`op_names` reads the trace's protobuf (``XSpace``) itself, by field
number:

  XSpace          1 planes
  XPlane          2 name, 4 event_metadata (map), 5 stat_metadata (map)
  map entry       1 key, 2 value
  XEventMetadata  2 name (the instruction's HLO text), 5 stats
  XStatMetadata   2 name
  XStat           1 metadata id, 5 string value, 7 ref (a stat name's id)

A device operation is keyed by its instruction's HLO text, the name its
events carry (``%fusion.32 = f32[...] fusion(...), kind=...``).
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Iterator, Optional, Tuple

from trace_reduce import DEVICE_PLANE, OPS_LINE

VJP_SCOPE = re.compile(r"repro_kernel_vjp:([\w.-]+)")
OP_NAME_STAT = "tf_op"


def vjp_tag(op_name: str) -> Optional[str]:
    """The ``repro_kernel_vjp:<tag>`` an op_name lies under, if any."""
    m = VJP_SCOPE.search(op_name)
    return m.group(1) if m else None


def scope_map(ops: Iterable[Tuple[str, str]]) -> Dict[str, str]:
    """``{HLO text: tag}`` of the ``(HLO text, op_name)`` pairs of device
    operations whose op_name lies under a ``repro_kernel_vjp:`` scope."""
    out = {}
    for hlo, op_name in ops:
        tag = vjp_tag(op_name)
        if tag is not None:
            out[hlo] = tag
    return out


# ------------------------------------------------ protobuf wire format
def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field, skipped fixed-width ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names(path: str) -> Dict[str, str]:
    """``{HLO text of a device operation: its op_name}`` over the trace's
    device planes (operations without an op_name left out)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                entry = dict(_fields(pv))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        if not DEVICE_PLANE.match(name):
            continue
        want = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        for entry in events:
            hlo, op = "", ""
            for mf, mv in _fields(dict(_fields(entry)).get(2, b"")):
                if mf == 2:
                    hlo = _text(mv)
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if stat.get(1) in want:
                        op = (_text(stat[5]) if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if op:
                out[hlo] = op
    return out


def vjp_seconds(path: str, window_span: str) -> Optional[Dict[str, float]]:
    """Device seconds, within the one ``window_span`` host span and averaged
    over the trace's devices, of the operations under each
    ``repro_kernel_vjp:<tag>`` scope: ``{tag: seconds}``, None where the
    trace holds no such window or no device operations."""
    from jax.profiler import ProfileData

    scopes = scope_map(op_names(path).items())
    data = ProfileData.from_file(path)
    windows, devices = [], {}
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                devices.setdefault(int(dev.group(1)), []).extend(
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                windows.extend(
                    (ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events if ev.name == window_span)
    if len(windows) != 1 or not devices:
        return None
    lo, hi = windows[0]
    out: Dict[str, float] = {}
    for ops in devices.values():
        for name, s, e in ops:
            tag = scopes.get(name)
            if tag is not None and e > lo and s < hi:
                out[tag] = out.get(tag, 0.0) + min(e, hi) - max(s, lo)
    return {tag: t / len(devices) for tag, t in out.items()}
