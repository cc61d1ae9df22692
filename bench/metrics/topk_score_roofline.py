"""topk_score_roofline: the least time of the window's ``topk_score``
calls over their device time, in percent.

Device time: the summed duration of the kernel's operations in the trace
(``KERNEL``, the name the Pallas kernel's custom call carries there:
``%topk_score.1 = (f32[B,128], s32[B,128]) custom-call(...)``). Least time: per
shard call, the larger of its FLOPs over the bf16 peak and its bytes over
the HBM bandwidth (``counts.topk_score_work``: ψ once, φ, the exclusion
ids, the outputs), summed over the flushes in the window."""
from bench import counts, tracing

KERNEL = "topk_score"


def read(rec):
    ops, win = rec.get("ops"), rec.get("window_ns")
    if not ops or win is None or not rec.get("flush_rows"):
        return None
    measured = sum(tracing.kernel_ns(e, KERNEL, *win) for e in ops) * 1e-9
    if measured <= 0:
        return None
    rows_per = -(-rec["n_items"] // rec["shards"])
    least = 0.0
    for b in rec["flush_rows"]:
        fl, by = counts.topk_score_work(b, rows_per, rec["dim"], rec["k"],
                                        rec["excl_l"])
        least += rec["shards"] * counts.least_time(fl, by, rec["peak"])[0]
    return 100.0 * least / measured
