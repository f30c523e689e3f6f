"""Read the program's own spans and the device ops' scopes from a run's
trace.

The program writes host spans named ``repro.<layer>.<what>`` with
integer counts (``repro.runtime.tracing``) and names its device work
with ``jax.named_scope``. A traced run's ``.xplane.pb`` holds both: the
spans on the host planes, each device op on its chip's ``XLA Ops`` line
(asynchronous ones from start to done on ``Async XLA Ops``), and, on the
``/host:metadata`` plane, the compiled HLO of every program, whose
instructions carry the scopes as ``op_name`` metadata. An op's scope is
read from there, by its program and its instruction name.

Ops nest on a chip's line (a ``while`` holds its body's ops), so device
time by scope is counted as self time: an op's interval less the ops
nested inside it on the same chip.

The file is the one the run's ``Tracer`` wrote; it is parsed once per
run, and only if its ``bench.traced`` span is the run's traced window.

``open_at`` and ``idle_gaps`` feed no metric: they serve the analysis
that PERF.md quotes, run on a traced run's directory as

    python3 -m bench.lib.program_trace [<log dir>] [<scope> ...]

which prints, as one JSON object, the device seconds under each scope
(``serve_step/attn`` names a scope inside one program) and the longest
idle gaps, each named by the program span or compile open in it.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys

from bench.lib import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".bench_runs", "trace")  # where run.py's Tracer writes
PREFIX = "repro."
COMPILE = "backend_compile"  # JAX's host event around each compile


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span on the host: ``repro.`` stripped, ns."""

    name: str
    start: float
    end: float
    counts: dict


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op: its scope path and the intervals (ns) of its self
    time; an asynchronous op's self time is its whole interval."""

    scope: tuple[str, ...]
    self_time: tuple[tuple[float, float], ...]


@dataclasses.dataclass
class ProgramTrace:
    window: tuple[float, float]  # ns, the ``bench.traced`` span
    spans: list[Span]  # the program's host spans
    chips: dict[str, list[Op]]  # per chip: sync and async ops
    compiles: list[tr.Event]  # JAX's compile events on the host

    def spans_in_window(self, name: str) -> list[Span]:
        """Spans named ``name`` that lie wholly in the traced window."""
        lo, hi = self.window
        return [s for s in self.spans if s.name == name and s.start >= lo and s.end <= hi]


# -- scopes ------------------------------------------------------------------------

_WRAPPER = re.compile(r"^(\w+\()+|\)+$")


def scope_path(op_name: str) -> tuple[str, ...]:
    """``jit(step)/shard_map/transpose(jvp(attn))/dot`` -> the parts
    with the transformations that wrap a scope taken off: ``('step',
    'shard_map', 'attn', 'dot')``."""
    return tuple(_WRAPPER.sub("", p) for p in op_name.split("/") if p)


def in_scope(path: tuple[str, ...], scope: str) -> bool:
    """``scope`` (``grad_reduce/chain``) names parts that ``path`` holds
    in this order, not necessarily next to one another."""
    it = iter(path)
    return all(part in it for part in scope.split("/"))


# -- self time -----------------------------------------------------------------------


def self_times(events: list[tuple[float, float]]) -> list[list[tuple[float, float]]]:
    """For each ``(start, end)``, the parts of it that no other event
    nested inside it covers. An event that starts inside another counts
    as nested in it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    children: list[list[int]] = [[] for _ in events]
    stack: list[int] = []
    for i in order:
        while stack and events[stack[-1]][1] <= events[i][0]:
            stack.pop()
        if stack:
            children[stack[-1]].append(i)
        stack.append(i)
    return [tr.gaps(tr.merge(events[j] for j in children[i]), *events[i])
            for i in range(len(events))]


def scoped_ns(pt: ProgramTrace, scope: str) -> float:
    """Device ns under ``scope`` in the traced window: on each chip the
    union of the self time of its ops under the scope, averaged over
    the chips."""
    if not pt.chips:
        return 0.0
    total = 0.0
    for ops in pt.chips.values():
        spans = [iv for op in ops if in_scope(op.scope, scope) for iv in op.self_time]
        total += tr.length(tr.clip(tr.merge(spans), *pt.window))
    return total / len(pt.chips)


def device_ns_in(trace: tr.Trace, spans: list[Span]) -> list[float]:
    """Per span, device ns of the compiled programs inside it (each
    chip's programs clipped to the span), averaged over the chips."""
    mods = [tr.merge(d.modules) for d in trace.devices.values()]
    return [sum(tr.length(tr.clip(m, s.start, s.end)) for m in mods) / len(mods) for s in spans]


def open_at(pt: ProgramTrace, t: float) -> str:
    """The innermost program span or compile open at ``t``, or ``none``."""
    opened = [tr.Event(s.start, s.end, s.name) for s in pt.spans]
    opened += [tr.Event(c.start, c.end, "compile") for c in pt.compiles]
    return tr.span_at(opened, t)


def idle_gaps(pt: ProgramTrace, trace: tr.Trace, top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps of the first chip, each named by the
    innermost program span (or compile) open at its middle."""
    if not trace.devices:
        return []
    dev = trace.devices[sorted(trace.devices)[0]]
    g = sorted(tr.gaps(tr.busy(dev, pt.window), *pt.window), key=lambda x: x[0] - x[1])[:top]
    return [[open_at(pt, (s + e) / 2), (e - s) * tr.NS] for s, e in g]


# -- the HLO on the metadata plane -------------------------------------------------------
#
# ``jax.profiler.ProfileData`` gives no access to a plane's event
# metadata, where the HLO lives, so the few messages needed are read
# straight off the protobuf wire format. Field numbers are those of
# ``xplane.proto`` and ``hlo.proto``.


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of a message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i: i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _one(buf: bytes, field: int, default=b""):
    for f, v in _fields(buf):
        if f == field:
            return v
    return default


def hlo_op_names(hlo_proto: bytes) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata over every computation
    of a serialized ``HloProto``."""
    names: dict[str, str] = {}
    for f, comp in _fields(_one(hlo_proto, 1)):  # HloProto.hlo_module
        if f != 3:  # HloModuleProto.computations
            continue
        for cf, inst in _fields(comp):
            if cf != 2:  # HloComputationProto.instructions
                continue
            name = op_name = ""
            for inf, value in _fields(inst):
                if inf == 1:  # HloInstructionProto.name
                    name = bytes(value).decode()
                elif inf == 7:  # HloInstructionProto.metadata: OpMetadata.op_name
                    op_name = bytes(_one(value, 2)).decode()
            names[name] = op_name
    return names


def programs_hlo(xspace: bytes) -> dict[str, dict[str, str]]:
    """Program name as the trace's ``XLA Modules`` events give it
    (``jit_step(<id>)``) -> its instructions' ``op_name``s, from the
    ``/host:metadata`` plane."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1 or bytes(_one(plane, 2)) != b"/host:metadata":
            continue
        for pf, entry in _fields(plane):
            if pf != 4:  # event_metadata map entry: key 1, value 2
                continue
            meta = _one(entry, 2)
            name = bytes(_one(meta, 2)).decode()
            for sf, stat in _fields(meta):
                if sf == 5:  # XStat; the plane has one stat kind, "Hlo Proto"
                    proto = _one(stat, 6)
                    if proto:
                        out[name] = hlo_op_names(bytes(proto))
    return out


# -- loading -----------------------------------------------------------------------------

_cache: dict = {}


def read(path: str) -> ProgramTrace:
    """Parse one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    hlo = programs_hlo(memoryview(raw))
    data = ProfileData.from_serialized_xspace(raw)
    spans: list[Span] = []
    compiles: list[tr.Event] = []
    window = None
    chips: dict[str, list[Op]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chips[plane.name] = _chip_ops(plane, hlo)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append(Span(e.name[len(PREFIX):], e.start_ns, e.end_ns,
                                          {k: int(v) for k, v in e.stats}))
                    elif e.name == "bench.traced":
                        window = (e.start_ns, e.end_ns)
                    elif COMPILE in e.name:
                        compiles.append(tr.Event(e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError("the trace holds no bench.traced span")
    return ProgramTrace(window=window, spans=sorted(spans, key=lambda s: s.start),
                        chips=chips, compiles=compiles)


def _chip_ops(plane, hlo: dict[str, dict[str, str]]) -> list[Op]:
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted(lines.get("XLA Modules", []), key=lambda e: e.start_ns)

    def scopes(events):
        """Each event's scope path, from the program running at its start
        (``XLA Modules`` events and the metadata plane share its name,
        ``jit_step(<program id>)``)."""
        out, j = [], 0
        for e in events:
            while j < len(mods) and mods[j].end_ns < e.start_ns:
                j += 1
            prog = mods[j].name if j < len(mods) and mods[j].start_ns <= e.start_ns else ""
            out.append(scope_path(hlo.get(prog, {}).get(tr.op_name(e.name), "")))
        return out

    sync = sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns)
    ops = [Op(scope, tuple(own)) for scope, own in zip(
        scopes(sync), self_times([(e.start_ns, e.end_ns) for e in sync]))]
    asyn = sorted(lines.get("Async XLA Ops", []), key=lambda e: e.start_ns)
    ops += [Op(scope, ((e.start_ns, e.end_ns),)) for scope, e in zip(scopes(asyn), asyn)]
    return ops


def load(run, log_dir: str = TRACE_DIR) -> ProgramTrace | None:
    """The program trace of ``run``'s traced window, or ``None`` where
    the run traced no chip (a rehearsal on the CPU) or the file on disk
    is another run's."""
    if run.trace is None or not run.trace.devices:
        return None
    try:
        path = tr.xplane_file(log_dir)
        st = os.stat(path)
    except OSError:
        return None
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = read(path)
    pt = _cache[key]
    return pt if tuple(pt.window) == tuple(run.trace.window) else None


SCOPES = ("attn", "mlp", "lm_head", "grad_reduce", "grad_reduce/chain", "optimizer")


def main(argv: list[str]) -> None:
    log_dir = argv[0] if argv else TRACE_DIR
    pt = read(tr.xplane_file(log_dir))
    trace = tr.load(log_dir)
    print(json.dumps({
        "window_s": (pt.window[1] - pt.window[0]) * tr.NS,
        "busy_s": tr.busy_s(trace),
        "scope_s": {s: scoped_ns(pt, s) * tr.NS for s in argv[1:] or SCOPES},
        "idle_gaps": idle_gaps(pt, trace),
    }))


if __name__ == "__main__":
    main(sys.argv[1:])
