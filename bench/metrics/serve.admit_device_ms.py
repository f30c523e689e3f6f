"""Device time of one admission: the compiled programs that ran inside
each of the program's ``serve.admit_request`` spans in the traced
window (prefix seeding, suffix decode or prefill, the cache-row write),
each chip's programs clipped to the span, averaged over the requests.
Beside the host time of an admission it says how much of it the chip
was busy."""

from bench.lib import program_trace


def read(run):
    pt = program_trace.load(run)
    admits = pt.spans_in_window("serve.admit_request") if pt else []
    if not admits:
        return None
    return sum(program_trace.device_ns_in(run.trace, admits)) / len(admits) * 1e-6
