"""Host milliseconds per ``FilterbankEngine.flush()`` spent moving the
signal: the program's ``repro.fir.to_device`` span (codes to the device)
and ``repro.fir.fetch`` span (the wait for the dispatch and the copy
back), self time over the traced window, per ``repro.fir.flush`` call.
A program that keeps no span table leaves the metric out."""
PHASES = ("to_device", "fetch")


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
