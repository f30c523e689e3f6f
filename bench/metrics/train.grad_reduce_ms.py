"""Device time of the Torrent gradient reduce per training step: on each
chip the union of the self time of the ops under the ``grad_reduce``
scope (the bucket packing, the chain's permutes from start to done and
the work between them, the unpacking and averaging; not the tensor-
parallel all-gathers), averaged over the chips, over the program's
``train.step`` spans in the traced window."""

from bench.lib import program_trace


def read(run):
    pt = program_trace.load(run)
    steps = pt.spans_in_window("train.step") if pt else []
    if not steps or not pt.chips:
        return None
    return program_trace.scoped_ns(pt, "grad_reduce") / len(steps) * 1e-6
