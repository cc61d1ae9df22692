"""serve.recover_ms: the time from the first failed ``dispatch`` of the
window (the lost replica's) to the end of the last ``heal`` span begun
after it, the one that restored the replication (program spans,
``repro.obs.Tracer``, host clock), in milliseconds. A heal span ends when
the new replica's slab is resident and it enters routing."""


def read(rec):
    spans = rec.get("program_spans") or []
    failed = [sp.t0 for sp in spans if sp.name == "dispatch"
              and sp.attrs.get("outcome", "ok") != "ok"]
    if not failed:
        return None
    t_fail = min(failed)
    ends = [sp.t1 for sp in spans if sp.name == "heal" and sp.t0 >= t_fail
            and sp.t1 is not None]
    return 1e3 * (max(ends) - t_fail) if ends else None
