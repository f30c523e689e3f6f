"""Device time of collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all and their async parts) per
training step: their union on each chip in the traced window, averaged
over the chips, over the steps that ran in it."""

from bench.lib import trace as tr


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    steps = [s for s in run.trace.spans if s.name == "train_step"]
    if not steps:
        return None
    total = sum(tr.collective_split(d, run.trace.window)[0] for d in run.trace.devices.values())
    return total / len(run.trace.devices) / len(steps) * 1e-6
