"""Host time of a training step outside its device sync: each of the
program's ``train.step`` spans in the traced window less its
``train.sync`` child (the read of the loss), averaged over the steps.
It holds the host batch, the dispatch of the step and the straggler
bookkeeping."""

from bench.lib import program_trace


def read(run):
    pt = program_trace.load(run)
    steps = pt.spans_in_window("train.step") if pt else []
    if not steps:
        return None
    syncs = pt.spans_in_window("train.sync")
    host = 0.0
    for s in steps:
        inner = sum(c.end - c.start for c in syncs if c.start >= s.start and c.end <= s.end)
        host += s.end - s.start - inner
    return host / len(steps) * 1e-6
