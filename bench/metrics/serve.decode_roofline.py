"""The batched decode step's share of its roofline: the least time the
chip could take for each traced step, the larger of its least bytes
(every weight once, of an unshared embedding table only the batch's
rows, and the cached keys and values of the live positions that each
row attends to) over HBM bandwidth and its operations over the bf16
peak, averaged over the steps and divided by the measured device time
per step. At these batch sizes the bytes bound it."""

from bench.lib import trace as tr


def read(run):
    steps = run.spans_in_trace("decode_step")
    if run.trace is None or not steps:
        return None
    total_ns, n = tr.device_time_in_spans(run.trace, "decode_step")
    if not n:
        return None
    least = sum(max(s.nbytes / run.peaks["hbm_bytes_per_s"], s.flops / run.peaks["bf16_flops_per_s"])
                for s in steps) / len(steps)
    return 100.0 * least / (total_ns / n * 1e-9)
