"""serve.mfu: the useful scoring FLOPs of the window's flushes (2·rows·N·D,
real rows only) over the summed flush wall time (program spans) and the
chip's bf16 peak, in percent."""


def read(rec):
    fl = [sp for sp in rec.get("program_spans") or [] if sp.name == "flush"]
    t = sum(sp.duration for sp in fl)
    if not fl or t <= 0:
        return None
    flops = sum(2 * sp.attrs["batch"] * rec["n_items"] * rec["dim"]
                for sp in fl)
    return 100.0 * flops / (t * rec["peak"]["bf16_flops_per_s"])
