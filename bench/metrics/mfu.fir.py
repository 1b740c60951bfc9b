"""The filter's nominal operations (2 x taps per output sample returned)
over the summed wall time of every flush, as a share of the chip's int8
peak: the whole served step, host work included."""
from bench import counts


def read(run):
    s = run.host.get("flush_s")
    if not s:
        return None
    ops, _ = counts.fir_nominal(run.counters["samples"], run.counters["taps"])
    return 100.0 * ops / sum(s) / run.peaks["int8_ops"]
