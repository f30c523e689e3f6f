"""Share of prompt tokens admitted from the prefix cache: the
``cached_tokens`` (KV rows seeded from a registered prefix) over the
``prompt_tokens`` of the program's ``serve.admit_request`` spans in the
traced window."""

from bench.lib import program_trace


def read(run):
    pt = program_trace.load(run)
    admits = pt.spans_in_window("serve.admit_request") if pt else []
    prompt = sum(s.counts["prompt_tokens"] for s in admits)
    if not prompt:
        return None
    return 100.0 * sum(s.counts["cached_tokens"] for s in admits) / prompt
