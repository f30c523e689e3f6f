"""Reduce a profiler trace to device busy time, idle gaps and op times.

A TPU trace (``jax.profiler``) has one plane per chip
(``/device:TPU:<n>``) whose ``XLA Ops`` line holds every operation the
chip ran, whose ``Async XLA Ops`` line holds each asynchronous operation
from its start to its done, and whose ``XLA Modules`` line holds every
compiled program, on the same clock as the host plane. The benchmark's own spans are
``jax.profiler.TraceAnnotation``s named ``bench.<what>`` on the host;
``bench.traced`` encloses the traced window.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

NS = 1e-9
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv|collective-broadcast)"
)


@dataclasses.dataclass(frozen=True)
class Event:
    start: float  # ns
    end: float  # ns
    name: str


@dataclasses.dataclass
class Device:
    ops: list[Event]
    modules: list[Event]
    # asynchronous operations (``Async XLA Ops``), each from its start to its done
    async_ops: list[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]  # ns, the ``bench.traced`` span
    devices: dict[str, Device]
    spans: list[Event]  # the benchmark's host spans, ``bench.`` stripped

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * NS


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``all-reduce-start.3`` -> ``all-reduce-start``."""
    return re.sub(r"(\.\d+)+$", "", name)


def module_name(name: str) -> str:
    """``jit_serve_step(3144510844975366864)`` -> ``jit_serve_step``."""
    return name.split("(", 1)[0]


def is_collective(op: str) -> bool:
    return bool(COLLECTIVE.match(op))


# -- interval arithmetic ---------------------------------------------------------


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted((i.start, i.end) if isinstance(i, Event) else i for i in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi)`` between merged busy intervals."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: list[Event], t: float) -> str:
    """The innermost benchmark span open at ``t``, or ``none``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else "none"


# -- reductions -----------------------------------------------------------------


def busy(dev: Device, window) -> list[tuple[float, float]]:
    return clip(merge(dev.ops), *window)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return sum(length(busy(d, trace.window)) for d in trace.devices.values()) * NS / len(trace.devices)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def collective_split(dev: Device, window) -> tuple[float, float]:
    """(collective ns, of which exposed ns) on one chip: the union of
    collective ops, async ones from start to done, and its part in which
    no other op runs."""
    coll = clip(merge(e for e in dev.ops + dev.async_ops if is_collective(e.name)), *window)
    comp = clip(merge(e for e in dev.ops if not is_collective(e.name)), *window)
    total = length(coll)
    return total, total - length(intersect(coll, comp))


def op_seconds(trace: Trace, top: int = 10) -> list[list]:
    """The ``top`` (program/op kind, seconds) by device time, averaged
    over the chips."""
    acc: dict[str, float] = {}
    for dev in trace.devices.values():
        mods = sorted(dev.modules, key=lambda e: e.start)
        j = 0
        for ev in sorted(dev.ops, key=lambda e: e.start):
            if ev.end <= trace.window[0] or ev.start >= trace.window[1]:
                continue
            while j < len(mods) and mods[j].end < ev.start:
                j += 1
            mod = module_name(mods[j].name) if j < len(mods) and mods[j].start <= ev.start else "?"
            key = f"{mod}/{op_kind(ev.name)}"
            acc[key] = acc.get(key, 0.0) + (min(ev.end, trace.window[1]) - max(ev.start, trace.window[0]))
    n = max(1, len(trace.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * NS / n] for k, v in ranked]


def idle_gaps(trace: Trace, top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps of the first chip, each named by
    the benchmark span open at its middle."""
    if not trace.devices:
        return []
    dev = trace.devices[sorted(trace.devices)[0]]
    g = sorted(gaps(busy(dev, trace.window), *trace.window), key=lambda x: x[0] - x[1])[:top]
    return [[span_at(trace.spans, (s + e) / 2), (e - s) * NS] for s, e in g]


# -- loading ---------------------------------------------------------------------


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return found[-1]


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_file(log_dir))
    devices: dict[str, Device] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(ops=[], modules=[])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [Event(e.start_ns, e.end_ns, op_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [Event(e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "Async XLA Ops":
                    dev.async_ops = [Event(e.start_ns, e.end_ns, op_name(e.name)) for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Event(e.start_ns, e.end_ns, e.name[len("bench."):]))
    traced = [s for s in spans if s.name == "traced"]
    if not traced:
        raise ValueError("the trace holds no bench.traced span")
    window = (traced[0].start, traced[0].end)
    return Trace(window=window, devices=devices, spans=[s for s in spans if s.name != "traced"])


def device_time_in_spans(trace: Trace, name: str) -> tuple[float, int]:
    """(device ns of compiled programs inside the benchmark's ``name``
    spans, averaged over the chips; number of such spans that ran a
    program)."""
    devices = list(trace.devices.values())
    mods = [merge(d.modules) for d in devices]
    total, n = 0.0, 0
    for s in trace.spans:
        if s.name != name:
            continue
        t = sum(length(clip(m, s.start, s.end)) for m in mods)
        if t > 0:
            total += t / len(devices)
            n += 1
    return total, n
