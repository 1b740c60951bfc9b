"""Share of its roofline reached by the jitted filterbank dispatch.

Least time over measured device time.  The least time is the larger of the
filter's nominal operations (2 x taps per output sample) over the int8
peak and its nominal bytes (a 2-byte code in, a 4-byte accumulator out per
sample) over HBM bandwidth; at 31 taps the bytes bound it.  Device time is
the sum of the ``jit_fir_bbm_bank_precoded`` program's events in the
trace.  The work counts the filter, whatever implements it.
"""
from bench import counts
from bench.trace import program_seconds

PROGRAM = "jit_fir_bbm_bank_precoded"


def read(run):
    if run.reduced is None:
        return None
    dev_s, calls = program_seconds(run.reduced, PROGRAM)
    if calls == 0 or dev_s <= 0:
        return None
    per_call = run.counters["samples"] / run.counters["flushes"]
    ops, nbytes = counts.fir_nominal(per_call * calls, run.counters["taps"])
    pk = run.peaks
    least, bound = counts.roofline_least_s(ops, nbytes, pk["int8_ops"],
                                           pk["hbm_bytes_per_s"])
    run.counters["fir_dispatch_bound"] = bound
    return 100.0 * least / dev_s
