"""serve.device_skew: 100 × the ok ``dispatch`` count of the busiest chip
over the mean count of the chips that held a slab at the window's start
(program spans, each dispatch's ``device``), over the window, in percent:
100 where the mesh spreads its work evenly.

It also logs the counts before the first failed dispatch and after it,
and each chip's busy seconds from the device trace."""
from bench import program_trace, tracing


def skew(counts: dict, chips) -> float | None:
    mean = sum(counts.get(c, 0) for c in chips) / len(chips)
    return 100.0 * max(counts.get(c, 0) for c in chips) / mean if mean \
        else None


def read(rec):
    spans = rec.get("program_spans") or []
    ok = [sp for sp in spans if sp.name == "dispatch"
          and sp.attrs.get("outcome") == "ok" and "device" in sp.attrs]
    if not ok:
        return None
    chips = rec.get("slab_devices") or sorted({sp.attrs["device"]
                                               for sp in ok})
    failed = [sp.t0 for sp in spans if sp.name == "dispatch"
              and sp.attrs.get("outcome", "ok") != "ok"]
    t_fail = min(failed, default=float("inf"))
    split = {}
    for part, sel in (("all", ok), ("before", [sp for sp in ok
                                               if sp.t0 < t_fail]),
                      ("after", [sp for sp in ok if sp.t0 >= t_fail])):
        counts = {}
        for sp in sel:
            counts[sp.attrs["device"]] = counts.get(sp.attrs["device"], 0) + 1
        split[part] = (counts, skew(counts, chips))
    ops, win, ids = rec.get("ops"), rec.get("window_ns"), rec.get("device_ids")
    busy = ({i: tracing.busy_ns(e, *win) * 1e-9 for i, e in zip(ids, ops)}
            if ops and win is not None and ids else None)
    program_trace.log(f"dispatches by chip (counts, skew %) over the "
                      f"window, before and after the first failure: {split};"
                      f" busy seconds by chip (device trace): {busy}")
    return split["all"][1]
