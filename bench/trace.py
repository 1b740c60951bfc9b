"""Profiler capture of the measured window, and its reduction to numbers.

The harness marks its own calls into each layer with host spans named
``bench.<what>`` (``span``).  A traced run records its window with
``jax.profiler``, inside a ``bench.traced`` span.  ``load_events`` flattens the
trace to plain event dicts and ``reduce`` turns them into device busy
time, per-program device time, the top device operations and the idle
gaps, each attributed to the host span it fell in.  ``reduce`` takes plain
dicts so that it can be checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import collections
import glob
import itertools
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def span(name: str):
    """A host span around one call into a layer (cheap when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Capture:
    """``with Capture(on) as cap:`` traces the block when ``on``;
    ``cap.events`` holds the flattened trace afterwards (else None), and
    ``cap.seconds`` what stopping and reading it took."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.events = None
        self.seconds = {}
        self._dir = None
        self._span = None

    def __enter__(self):
        if self.enabled:
            import jax
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            # the Python tracer records every Python call and slows the
            # host loop several times over; the harness's spans and the
            # device's events need only the host and device tracers
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._span = span("traced")
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax
            self._span.__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            t1 = time.perf_counter()
            try:
                self.events = load_events(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
            self.seconds = {"stop_trace": t1 - t0,
                            "load_events": time.perf_counter() - t1}
        return False


def module_name(name: str) -> str:
    """"jit_decode(1234)" -> "jit_decode"."""
    return re.sub(r"\(\d+\)$", "", name)


def load_events(logdir: str) -> Dict[str, List[dict]]:
    """Flatten the newest ``.xplane.pb`` under ``logdir``.

    device: every event on a device plane's ops and modules lines (an op
    named by its HLO instruction, the text before " = ");
    host: every ``bench.*`` span on any host thread.
    """
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no trace written under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    device.append({
                        "plane": plane.name, "line": line.name,
                        "name": e.name.split(" = ", 1)[0],
                        "start": float(e.start_ns),
                        "dur": float(e.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append({"name": e.name,
                                     "start": float(e.start_ns),
                                     "dur": float(e.duration_ns)})
    return {"device": device, "host": host}


def _modules_at(events, plane: str):
    """(starts, ends, names) of the programs that ran on ``plane``."""
    mods = sorted((e["start"], e["start"] + e["dur"],
                   module_name(e["name"])) for e in events
                  if e["plane"] == plane and e["line"] == MODULES_LINE)
    return ([a for a, _, _ in mods], [b for _, b, _ in mods],
            [n for _, _, n in mods])


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce(events: Dict[str, List[dict]], top: int = 10) -> dict:
    """Numbers of one traced window (all times in seconds).

    window_s     length of the ``bench.window`` host span
    busy_s       union of device op intervals inside it, averaged over the
                 device planes that ran anything
    programs     {program: {"s": device seconds, "calls": n}} from the
                 modules line, a call counted where it starts in the window
    device_ops   the ``top`` ops by device seconds, "program:op"
    idle_gaps    the ``top`` host spans by the device idle time that fell
                 inside them ("bench.untracked" where none was open)
    """
    wins = [h for h in events["host"] if h["name"] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w = max(wins, key=lambda h: h["dur"])
    lo, hi = w["start"], w["start"] + w["dur"]
    ns = 1e-9

    by_plane = collections.defaultdict(list)
    programs: Dict[str, dict] = {}
    ops = collections.Counter()
    mods = {}
    for e in events["device"]:
        iv = _clip(e["start"], e["start"] + e["dur"], lo, hi)
        if e["line"] == MODULES_LINE:
            if lo <= e["start"] < hi:
                p = programs.setdefault(module_name(e["name"]),
                                        {"s": 0.0, "calls": 0})
                p["s"] += e["dur"] * ns
                p["calls"] += 1
            continue
        if iv is None:
            continue
        by_plane[e["plane"]].append(iv)
        if e["plane"] not in mods:
            mods[e["plane"]] = _modules_at(events["device"], e["plane"])
        starts, ends, names = mods[e["plane"]]
        j = bisect.bisect_right(starts, e["start"]) - 1
        prog = names[j] if j >= 0 and e["start"] < ends[j] else "?"
        ops[f"{prog}:{e['name']}"] += (iv[1] - iv[0]) * ns

    busy, gaps_first = [], []
    for i, plane in enumerate(sorted(by_plane)):
        u = _union(by_plane[plane])
        busy.append(sum(b - a for a, b in u) * ns)
        if i == 0:
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            gaps_first = [(edges[j], edges[j + 1])
                          for j in range(0, len(edges), 2)
                          if edges[j + 1] > edges[j]]
    spans = sorted((h["start"], h["start"] + h["dur"], h["name"])
                   for h in events["host"] if h["name"] != WINDOW_SPAN)
    starts = [a for a, _, _ in spans]
    reach = list(itertools.accumulate((b for _, b, _ in spans), max))
    idle = collections.Counter()
    for g0, g1 in gaps_first:
        best, best_ov = SPAN_PREFIX + "untracked", 0.0
        j = bisect.bisect_left(starts, g1) - 1    # spans starting before g1
        while j >= 0 and reach[j] > g0:            # ... that may reach g0
            a, b, name = spans[j]
            iv = _clip(a, b, g0, g1)
            if iv and iv[1] - iv[0] >= best_ov:
                best, best_ov = name, iv[1] - iv[0]
            j -= 1
        idle[best] += (g1 - g0) * ns
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "programs": programs,
        "device_ops": [[k, v] for k, v in ops.most_common(top)],
        "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
    }


def program_seconds(reduced: dict, prefix: str):
    """(device seconds, calls) of the programs whose name starts with
    ``prefix`` (``jit_decode`` matches ``jit_decode`` only, not
    ``jit_decode_x``: the name is compared up to its end or a dot)."""
    s, calls = 0.0, 0
    for name, p in reduced["programs"].items():
        if name == prefix or name.startswith(prefix + "."):
            s += p["s"]
            calls += p["calls"]
    return s, calls
