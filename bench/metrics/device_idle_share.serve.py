"""Share of the traced window in which no operation ran on the chip."""

from bench.lib import trace as tr


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * tr.idle_share(run.trace)
