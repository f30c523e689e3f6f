"""Host time a request waited in the server's queue: from ``submit`` to
the start of its admission, the ``queued_us`` count of the program's
``serve.admit_request`` spans in the traced window, averaged over the
requests admitted there. Lateness of the load generator before
``submit`` is not in it: the metric sees a wait only where requests
queue behind full slots or behind other admissions of the same tick
(the unshared-docs cell), not where the loop submits each request after
the admission that delayed it (prefix-chat)."""

from bench.lib import program_trace


def read(run):
    pt = program_trace.load(run)
    admits = pt.spans_in_window("serve.admit_request") if pt else []
    if not admits:
        return None
    return sum(s.counts["queued_us"] for s in admits) / len(admits) * 1e-3
