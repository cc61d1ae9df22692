"""train.gather_ms: device milliseconds per training step of the ops under
the step's ``icd.gather``, ``icd.patch`` and ``icd.permute`` scopes (device
trace): the column gathers through the pair layout, the residual patch
and the layout permutations, all the traffic through the 20M-pair layout
other than its reductions. A fused op counts under its root's scope."""
from bench import program_trace


def read(rec):
    return program_trace.scope_ms_per_step(rec, program_trace.LAYOUT_SCOPES)
