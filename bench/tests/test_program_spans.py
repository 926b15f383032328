"""The program's spans and named scopes read from traces: the
op_name-to-scope map on a CPU-compiled function, ``kernel_vjp_ms`` on
hand-made records and traces and on a trace recorded on one v5e with the
program's tracer on, and ``trace_reduce.reduce`` pinned on the small
recorded trace."""

from __future__ import annotations

import os
import re
import shutil

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH

DATA = os.path.join(BENCH, "tests", "data")
TINY_TRACE = os.path.join(DATA, "tiny.xplane.pb")
SPANS_TRACE = os.path.join(DATA, "spans.xplane.pb")
SPANS = ("loader.next", "step.dispatch", "step.block")
# an HLO instruction with its op_name, as XLA prints them
INSTRUCTION = re.compile(r'^\s*(?:ROOT )?(%\S+ = .*?), metadata=\{op_name="([^"]*)"')


def _toy_step():
    @jax.custom_vjp
    def f(x):
        return jnp.sin(x) * 2.0

    def fwd(x):
        return f(x), x

    def bwd(x, g):
        with jax.named_scope("repro_kernel_vjp:toy"):
            return (jnp.cos(x) * 2.0 * g,)

    f.defvjp(fwd, bwd)
    return jax.jit(jax.grad(lambda x: (f(x) * x).sum()))


def test_scope_map_on_a_cpu_compiled_function():
    import trace_scopes as ts

    text = _toy_step().lower(jnp.ones((64, 64))).compile().as_text()
    ops = [m.groups() for m in map(INSTRUCTION.match, text.splitlines())
           if m]
    scoped = ts.scope_map(ops)
    assert scoped and set(scoped.values()) == {"toy"}
    # the map is keyed by the HLO text the trace names an operation by
    assert all(k.startswith("%") and " = " in k for k in scoped)
    # operations outside the backward's scope stay out
    assert len(scoped) < len(ops)
    assert ts.vjp_tag("jit(step)/transpose(jvp(repro_kernel_vjp:gat_ell))/"
                      "scatter-add") == "gat_ell"
    assert ts.vjp_tag("jit(step)/jvp(gat)/dot_general") is None


# ------------------------------------------- a hand-made XSpace trace
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf message from (field number, int | bytes | str) pairs."""
    out = bytearray()
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return bytes(out)


def _plane(name, line, events, metadata, stat_names=()):
    """XPlane with one line; ``metadata`` is [(id, HLO text, [stat])], a
    stat being (field, value) pairs; ``events`` [(metadata id, start ns,
    end ns)]."""
    evs = [(4, _msg((1, m), (2, s * 1000), (3, (e - s) * 1000)))
           for m, s, e in events]
    fields = [(2, name), (3, _msg((2, line), (3, 0), *evs))]
    for mid, text, stats in metadata:
        meta = _msg((1, mid), (2, text), *[(5, _msg(*st)) for st in stats])
        fields.append((4, _msg((1, mid), (2, meta))))
    for sid, sname in stat_names:
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*fields)


def _write_trace(path):
    scoped = "jit(step)/transpose(jvp(repro_kernel_vjp:gat_ell))/scatter-add"
    device = _plane(
        "/device:TPU:0", "XLA Ops",
        [(1, 1000, 3000), (2, 3000, 4000), (1, 5000, 6000)],
        [(1, "%fusion.32 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
          [((1, 7), (5, scoped))]),
         # an op_name given by reference to an interned string
         (2, "%fusion.33 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop",
          [((1, 7), (7, 9))])],
        stat_names=[(7, "tf_op"), (9, "jit(step)/transpose(jvp())/add")])
    host = _plane("/host:CPU", "python", [(1, 500, 5500)],
                  [(1, "bench.window", [])])
    with open(path, "wb") as f:
        f.write(_msg((1, device), (1, host)))


def test_vjp_seconds_on_a_hand_made_trace(tmp_path):
    import trace_scopes as ts

    path = str(tmp_path / "t.xplane.pb")
    _write_trace(path)
    names = ts.op_names(path)
    assert sorted(names.values()) == [
        "jit(step)/transpose(jvp())/add",
        "jit(step)/transpose(jvp(repro_kernel_vjp:gat_ell))/scatter-add"]
    # fusion.32 runs 2 us, then 1 us of which the window holds 0.5 us
    got = ts.vjp_seconds(path, "bench.window")
    assert list(got) == ["gat_ell"]
    assert got["gat_ell"] == pytest.approx(2.5e-6)
    assert ts.vjp_seconds(path, "no.such.window") is None
    ms = _reader().read({"steps": 2, "trace": {"window_s": 5e-6}},
                        trace_dir=str(tmp_path))
    assert ms == pytest.approx(1e3 * 2.5e-6 / 2)


def _reader():
    from harness import spec

    return spec.load_module("metrics", "kernel_vjp_ms")


def test_kernel_vjp_reader_on_hand_made_records(tmp_path):
    read = _reader().read
    rec = {"steps": 3, "trace": {"window_s": 1.0}}
    assert read({"steps": 3, "trace": None}, trace_dir=str(tmp_path)) is None
    assert read({"steps": 0, "trace": {}}, trace_dir=str(tmp_path)) is None
    # no trace file, and a trace with no backward in it
    assert read(rec, trace_dir=str(tmp_path)) is None
    shutil.copy(TINY_TRACE, tmp_path / "t.xplane.pb")
    assert read(rec, trace_dir=str(tmp_path)) is None


def test_reduce_of_the_tiny_trace_is_unchanged():
    import trace_reduce as tr

    red = tr.reduce(TINY_TRACE, "bench.window", SPANS)
    assert red["window_s"] == 0.009492929999999997
    assert red["devices"] == 1
    assert red["busy_s"] == 4.604299999998174e-05
    assert red["device_ops"] == [
        ("_spmm_ell_kernel", 4.2764999999993225e-05),
        ("copy.1 s32[64,8]", 1.5670000000009288e-06),
        ("convolution_reduce_fusion f32[] kOutput", 1.341000000001924e-06),
        ("reshape.0 s32[512]", 3.390000000089155e-07),
        ("copy-start f32[64,128]", 1.5999999991578306e-08),
        ("copy-done f32[64,128]", 8.999999995262797e-09),
        ("broadcast_in_dim.0 f32[8,8]", 5.999999996841865e-09)]
    assert dict(red["device_ops"]) == red["ops_s"]
    assert [lab for lab, _ in red["idle_gaps"]] == (
        ["step.block"] * 2 + ["loader.next"] * 8)
    assert red["idle_gaps"][:4] == [
        ("step.block", 0.0032857170000000005),
        ("step.block", 0.0031484339999999986),
        ("loader.next", 0.001723624),
        ("loader.next", 0.0012890939999999976)]


def test_recorded_v5e_trace_spans_and_backward(tmp_path):
    """A toy-sized ``gat-products.train`` run recorded on one v5e with the
    program's tracer on in the window, trimmed to the host spans and 300 of
    the window's device operations. The loader's spans come from their own
    threads under their bare names, with the batch index as a stat; the
    attention backward's operations carry their scope."""
    from jax.profiler import ProfileData

    import trace_reduce as tr
    import trace_scopes as ts

    lines, batches = {}, {}
    for plane in ProfileData.from_file(SPANS_TRACE).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                assert "#" not in ev.name
                lines.setdefault(ev.name, set()).add(i)
                batch = dict(ev.stats).get("batch")
                if batch is not None:
                    batches.setdefault(ev.name, set()).add(int(batch))
    main, = lines["bench.window"]
    assert lines["loader.wait"] == lines["loader.next"] == {main}
    producer, = lines["loader.sample"]
    assert producer != main and lines["loader.pack"] == {producer}
    # gathers run on the stage pool's threads
    assert len(lines["loader.gather"]) == 2
    assert not lines["loader.gather"] & {main, producer}
    # one batch's stage spans share its index
    assert {11, 12, 13} <= (batches["loader.sample"] & batches["loader.gather"]
                            & batches["loader.pack"])
    spans, _ = tr.read_events(SPANS_TRACE, ["loader.sample", "loader.wait"])
    assert len(spans["loader.sample"]) == 4 and spans["loader.wait"]
    got = ts.vjp_seconds(SPANS_TRACE, "bench.window")
    assert got == {"gat_ell": pytest.approx(0.01114716299999996)}
    shutil.copy(SPANS_TRACE, tmp_path / "t.xplane.pb")
    ms = _reader().read({"steps": 4, "trace": {"window_s": 0.056}},
                        trace_dir=str(tmp_path))
    assert ms == pytest.approx(1e3 * 0.01114716299999996 / 4)
