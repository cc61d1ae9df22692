"""serve.queue_ms: mean wait of a request in the batcher's queue, from its
``queue`` span's start to the start of the ``flush`` span that served it
(program spans, ``repro.obs.Tracer``, host clock), in milliseconds."""


def read(rec):
    spans = rec.get("program_spans") or []
    by_id = {sp.span_id: sp for sp in spans}
    waits = []
    for sp in spans:
        if sp.name != "queue":
            continue
        req = by_id.get(sp.parent_id)
        flush = by_id.get(req.attrs.get("flush_span")) if req else None
        if flush is not None:
            waits.append(flush.t0 - sp.t0)
    return 1e3 * sum(waits) / len(waits) if waits else None
