"""Model operations of every token the server processed in the traced
window (prompt tokens prefilled or decoded at admission, and each decoded
token), over the window's length times the chip's bf16 peak."""


def read(run):
    if run.traced is None:
        return None
    work = sum(s.flops for s in run.spans_in_trace("admit") + run.spans_in_trace("decode_step"))
    window = run.traced[1] - run.traced[0]
    if work <= 0 or window <= 0:
        return None
    return 100.0 * work / (window * run.chips * run.peaks["bf16_flops_per_s"])
