"""Host time of the benchmark's spans around admissions (``Server._admit``)
per request admitted, over the whole window. Where the prompt starts with
a cached prefix, an admission seeds the cached KV rows and decodes the
suffix a token at a time; where it shares nothing, it prefills the whole
prompt, which stalls the decode batch. Either way the time to first
token waits for it."""


def read(run):
    spans = [s for s in run.spans if s.kind == "admit"]
    n = sum(s.n for s in spans)
    return sum(s.t1 - s.t0 for s in spans) / n * 1e3 if n else None
