"""Schedule work per admitted request while serving: the self time of
the pipeline's ``sched.*`` spans (fingerprint, cache lookup, pack,
splice, harvest) over the requests admitted in the span phase of a
traced run, under the program's span tracer (see
``serve.host_ms_per_tick``)."""

import readers


def read(rec):
    spans = rec.get("spans")
    if not spans:
        return None
    admitted = sum(1 for s in spans if s.name == "cb.admit")
    if not admitted:
        return None
    own = readers.self_times(spans)
    return 1e3 * sum(v for n, v in own.items()
                     if n.startswith("sched.")) / admitted
