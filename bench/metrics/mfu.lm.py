"""Model FLOPs of the tokens processed in the window (prompt tokens
prefilled plus tokens decoded: 2 x matmul weights, tied LM head included,
plus attention scores and values at each token's context) over the window,
as a share of the chip's bf16 peak.  Emulated contractions do not count."""


def read(run):
    flops = run.counters.get("model_flops")
    if not flops or not run.window_s:
        return None
    return 100.0 * flops / run.window_s / run.peaks["bf16_flops"]
