"""What one run hands to the per-layer metric readers."""

from __future__ import annotations

import dataclasses
import importlib.util
import os

from bench.lib import trace as tr


@dataclasses.dataclass
class Span:
    """One call of the benchmark into a layer, on the host clock."""

    kind: str  # admit, decode_step, submit, train_step, ...
    t0: float  # time.perf_counter()
    t1: float
    n: int  # requests admitted, rows decoded, steps taken
    flops: float  # model operations the call performed
    nbytes: float = 0.0  # least bytes the call reads from memory, where counted


@dataclasses.dataclass
class RunData:
    """Everything a per-layer metric may read."""

    peaks: dict
    chips: int
    spans: list[Span]
    traced: tuple[float, float] | None  # host clock of the traced window
    trace: tr.Trace | None
    facts: dict

    def spans_in_trace(self, kind: str) -> list[Span]:
        """Spans of ``kind`` that lie wholly in the traced window."""
        if self.traced is None:
            return []
        lo, hi = self.traced
        return [s for s in self.spans if s.kind == kind and s.t0 >= lo and s.t1 <= hi]


def reader(root: str, metric: str):
    """The ``read(run) -> float | None`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

