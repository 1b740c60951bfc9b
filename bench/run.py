#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: every number that
decided ``correct``, beside its limit.  The same checks are the last lines
of standard error.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero: nothing here falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is measured from process start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import manifest as mf  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.peaks import peaks  # noqa: E402

COMPILE_EVENTS = ("backend_compile", "compilation_cache")
_COMPILES: list = []          # every compile or cache load in this process


def _on_event(name, dur, **kw):
    if any(k in name for k in COMPILE_EVENTS):
        _COMPILES.append(name)


_listening = False


class NoChip(RuntimeError):
    pass


def check_devices(chips: int):
    """The TPU devices this cell runs on; raises without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s), found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


class Run:
    """One run of one cell: what a runner fills in, what readers read.

    A runner calls ``window()`` around its measured loop (set-up ends where
    it is entered), records end-to-end numbers in ``metrics``, counts in
    ``counters``, host-clock samples in ``host``, and every comparison that
    decides ``correct`` through ``check``.
    """

    def __init__(self, cell: mf.Cell, seed: int, seconds: float,
                 trace: bool, devices):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace = trace
        self.devices = devices
        self.kind = devices[0].device_kind if devices else "none"
        self.metrics: dict = {}
        self.counters: dict = {}
        self.host: dict = {}
        self.checks: list = []
        self.attempted = 0
        self.failed = 0
        self.setup_s = None
        self.window_s = None
        self.memory_peak_bytes = None
        self.reduced = None
        self.compiles_in_window = 0
        self.controls: list = []      # control names to judge as well
        self.control_runs: dict = {}  # their judged copies of this run

    @property
    def peaks(self) -> dict:
        return peaks(self.kind)

    def check(self, name: str, value: float, limit: float,
              ok: bool | None = None):
        """One compared number; ``ok`` defaults to value <= limit."""
        value = float(value)
        self.checks.append({"name": name, "value": value,
                            "limit": float(limit),
                            "ok": bool(value <= limit if ok is None
                                       else ok)})

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends on entry; with ``--trace 1``
        the profiler records it."""
        global _listening
        if not _listening:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True
        self.setup_s = time.perf_counter() - T_START
        n0 = len(_COMPILES)
        with tr.Capture(self.trace) as cap:
            t0 = time.perf_counter()
            yield
            self.window_s = time.perf_counter() - t0
        self.compiles_in_window = len(_COMPILES) - n0
        if cap.events is not None:
            t = time.perf_counter()
            self.reduced = tr.reduce(cap.events)
            self.counters.update(
                {f"trace.{k}_s": v for k, v in cap.seconds.items()})
            self.counters["trace.reduce_s"] = time.perf_counter() - t
            self.counters["trace.device_events"] = len(cap.events["device"])

    def read_memory_peak(self):
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak or None


def result(run: Run) -> dict:
    """The result line's object (metrics chosen by ``run.trace``)."""
    e2e = {m["name"]: m for m in run.cell.end_to_end}
    values = dict(run.metrics)
    values["setup_s"] = run.setup_s
    metrics = {}
    if run.trace:
        for m in run.cell.per_layer:
            v = run.cell.reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for name, m in e2e.items():
            if values.get(name) is not None:
                metrics[name] = {"value": float(values[name]),
                                 "unit": m["unit"]}
    device = {"platform": run.devices[0].platform if run.devices else None,
              "kind": run.kind, "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": bool(run.checks) and all(c["ok"] for c in run.checks),
           "attempted": int(run.attempted), "failed": int(run.failed),
           "metrics": metrics, "device": device}
    if run.trace and run.reduced is not None:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        out["breakdown"] = {"device_ops": run.reduced["device_ops"],
                            "idle_gaps": run.reduced["idle_gaps"]}
    out["compiles_in_window"] = run.compiles_in_window
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in run.checks}
    return out


def compile_cache() -> None:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program however
    quick to compile, so the second run of a cell compiles nothing."""
    import jax
    from repro.launch import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(workload: str, seed: int, seconds: float, trace: bool):
    """Resolve the cell, look for its chips, run it; (result, run)."""
    cell = mf.resolve(mf.load_manifest(), workload)
    devices = check_devices(cell.chips)
    compile_cache()
    run = Run(cell, seed, seconds, trace, devices)
    cell.runner().run(run)
    return result(run), run


def report(out: dict, run: Run) -> None:
    print(f"counters {json.dumps(run.counters, default=str)}",
          file=sys.stderr)
    print(f"compiles_in_window {out['compiles_in_window']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        out, run = execute(args.workload, args.seed, args.seconds,
                           bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    report(out, run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
