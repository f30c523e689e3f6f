"""Share of the collective device time in which no other operation runs
on the same chip, over the traced window and all chips."""

from bench.lib import trace as tr


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    split = [tr.collective_split(d, run.trace.window) for d in run.trace.devices.values()]
    total = sum(t for t, _ in split)
    if total <= 0:
        return None
    return 100.0 * sum(e for _, e in split) / total
