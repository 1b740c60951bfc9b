"""95th percentile of the host-clock time of ``FilterbankEngine.flush()``,
over every flush in the window (layer: serve.FilterbankEngine)."""
import numpy as np


def read(run):
    s = run.host.get("flush_s")
    if not s:
        return None
    return float(np.percentile(np.asarray(s), 95)) * 1e3
