"""Share of its roofline reached by the decode step of a latent-attention
MoE model.

Least time over measured device time.  Each decode step of the window
has a least time, the larger of its model FLOPs over the bf16 peak and
its bytes over HBM bandwidth (``bench/counts_moe.py`` on the step's
logged routing: every weight read once, experts no live row was routed
to left out, and each live row's context of 2-byte latent codes).
Device time is the ``jit_decode`` program's events in the trace.  Their
mean least time over their mean device time; without a trace or without
the steps' work the metric is left out.
"""
from bench import counts
from bench.trace import program_seconds


def read(run):
    steps = run.host.get("decode_work")
    if run.reduced is None or not steps:
        return None
    dev_s, calls = program_seconds(run.reduced, "jit_decode")
    if calls == 0 or dev_s <= 0:
        return None
    pk = run.peaks
    least = sum(counts.roofline_least_s(f, b, pk["bf16_flops"],
                                        pk["hbm_bytes_per_s"])[0]
                for f, b in steps)
    return 100.0 * (least / len(steps)) / (dev_s / calls)
