"""Device time per batched decode step: the compiled programs that ran
inside the benchmark's ``decode_step`` spans (``Server.step``), per span,
in the traced window. The batch-1 suffix decodes of admissions run
inside ``admit`` spans and are not counted."""

from bench.lib import trace as tr


def read(run):
    if run.trace is None:
        return None
    total_ns, n = tr.device_time_in_spans(run.trace, "decode_step")
    return total_ns / n * 1e-6 if n else None
