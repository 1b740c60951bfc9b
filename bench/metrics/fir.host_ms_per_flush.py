"""Host milliseconds per ``FilterbankEngine.flush()`` spent in its own
float64 work: the self time of the program's ``repro.fir.stack``,
``repro.fir.quantize``, ``repro.fir.descale`` and ``repro.fir.split``
spans over the traced window, per ``repro.fir.flush`` call.  A program
that keeps no span table leaves the metric out."""
PHASES = ("stack", "quantize", "descale", "split")


def read(run):
    try:
        from repro.trace import recorded
    except ImportError:
        return None
    t = recorded()
    flush = t.get("repro.fir.flush")
    if not flush or not flush["calls"]:
        return None
    s = sum(t.get(f"repro.fir.{p}", {}).get("self_s", 0.0) for p in PHASES)
    return s / flush["calls"] * 1e3
