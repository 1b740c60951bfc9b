"""Device time per call of the jitted decode step (``jit_decode`` events
in the trace, summed and divided by their count)."""
from bench.trace import program_seconds


def read(run):
    if run.reduced is None:
        return None
    s, calls = program_seconds(run.reduced, "jit_decode")
    return s / calls * 1e3 if calls else None
