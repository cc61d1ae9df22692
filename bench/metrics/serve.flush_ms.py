"""serve.flush_ms: mean duration of the batcher's ``flush`` span (program
spans, host clock): forming the batch, the mesh's dispatches and the wait
for the results, in milliseconds."""


def read(rec):
    d = [sp.duration for sp in rec.get("program_spans") or []
         if sp.name == "flush"]
    return 1e3 * sum(d) / len(d) if d else None
