"""Device time of the jitted prefill (``jit_prefill`` events in the trace)
per prompt token prefilled in the window."""
from bench.trace import program_seconds


def read(run):
    if run.reduced is None:
        return None
    s, calls = program_seconds(run.reduced, "jit_prefill")
    n = run.counters.get("prefill_tokens")
    return s / n * 1e6 if calls and n else None
