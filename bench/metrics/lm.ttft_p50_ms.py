"""Median time from a client's send to its request's first token, over
the requests whose first token falls in the window (host clock): the
scheduler's FIFO wait for one admission a step, then the admitting step,
which carries the prompt's prefill.

A per-layer number, not an end-to-end one: a window holds about a dozen
first tokens, and which prompt lengths they have changes with the seed,
so the median lands on one of a few step lengths from run to run."""
import numpy as np


def read(run):
    ttft = run.host.get("ttft_s")
    if not ttft:
        return None
    return float(np.percentile(ttft, 50)) * 1e3
