"""train.segsum_ms: device milliseconds per training step of the ops under
the step's ``icd.segsum`` scope (device trace): the L'/L'' segment sums
over the pairs. A fused op counts under its root's scope."""
from bench import program_trace


def read(rec):
    return program_trace.scope_ms_per_step(rec, program_trace.SEGSUM_SCOPES)
