"""serve.flush_idle_ms: the mean, over the window's flushes, of the time
inside the batcher's ``flush`` span in which no operation ran on the
device, in milliseconds. The span is the program's ``repro.flush``
annotation, read from the device trace on its clock: this is what the
flush's host path costs the device, apart from the idle time between
flushes, which is light load.

It also logs the flush's phases (program spans, host clock) and the idle
gaps labelled by the innermost span of either prefix, ``bench.`` or
``repro.``."""
from bench import program_trace

GAP_MS = 1.0      # gaps this long are counted by label
LONG_MS = 20.0    # and these listed one by one


def read(rec):
    ops, win = rec.get("ops"), rec.get("window_ns")
    if not ops or win is None or not rec.get("trace_dir"):
        return None
    spans = program_trace.read(rec["trace_dir"])["spans"]
    idle = [x for events in ops
            for x in program_trace.flush_idle_ns(events, spans, *win)]
    if not idle:
        return None
    program_trace.log(
        "flush phases, mean ms (program spans, host clock): "
        f"{program_trace.flush_phases(rec.get('program_spans') or [])}")
    gaps = program_trace.labelled_gaps(ops[0], rec.get("spans", []) + spans,
                                       *win, min_ns=GAP_MS * 1e6)
    by = {}
    for label, _, ns in gaps:
        n, s = by.get(label, (0, 0.0))
        by[label] = (n + 1, s + ns * 1e-9)
    program_trace.log(f"idle gaps of {GAP_MS} ms or more by label "
                      f"(count, seconds): {by}")
    program_trace.log(
        f"idle gaps of {LONG_MS} ms or more (label, start s, ms): "
        f"{[(lab, (a - win[0]) * 1e-9, ns * 1e-6) for lab, a, ns in gaps if ns >= LONG_MS * 1e6]}")
    return 1e-6 * sum(idle) / len(idle)
