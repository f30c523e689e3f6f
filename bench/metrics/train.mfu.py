"""Model operations of the training steps completed in the traced window
(three times the forward: 6 x matmul parameters plus causal attention
scores per token; recomputation not counted), over the window's length
times the chips times the bf16 peak of one chip."""


def read(run):
    if run.traced is None:
        return None
    steps = run.spans_in_trace("train_step")
    window = run.traced[1] - run.traced[0]
    if not steps or window <= 0:
        return None
    return 100.0 * sum(s.flops for s in steps) / (window * run.chips * run.peaks["bf16_flops_per_s"])
