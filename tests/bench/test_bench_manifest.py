"""BENCHMARK.json and the files it names: charset, limits, resolution."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import manifest as mf  # noqa: E402

MAN = mf.load_manifest(ROOT)


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[key]:
            yield e["name"]
    for w in MAN["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in MAN["configs"]:
        yield from c["reduced"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_name_charset(name):
    assert mf.NAME_RE.match(name), name


def test_units_and_sources():
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert mf.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}


def test_unique_names():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in MAN[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_every_cell_resolves(cell):
    c = mf.resolve(MAN, cell)
    assert c.chips == 1
    assert (c.bench / "runners" / f"{c.config['runner']}.py").is_file()
    assert (c.bench / "reference" / f"{c.config['reference']}.py").is_file()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names
        assert hasattr(c.reader(m["name"]), "read")


def test_configs_name_their_files():
    for c in MAN["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert c["file"] == f"bench/configs/{c['name']}.json"


def test_dropped_in_cell_is_found(tmp_path):
    """A new cell, traffic mix and per-layer metric are new files and new
    entries only: nothing that is there is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    (b / "traffic" / "block4k.json").write_text(json.dumps(dict(
        json.loads((b / "traffic" / "block16k.json").read_text()),
        samples=4096)))
    (b / "workloads" / "fir30-bbm0.block4k.json").write_text(json.dumps({
        "config": "fir30-bbm0", "traffic": "block4k", "chips": 1,
        "why": "test", "check_requests": 4,
        "limits": {"mismatched_samples": 0}}))
    metric = "fir.flushes"
    (b / "metrics" / f"{metric}.py").write_text(
        "def read(run):\n    return run.counters.get('flushes')\n")
    for m in man["end_to_end"]:      # the new cell reports samples_per_s
        if m["name"] == "samples_per_s":
            m["workloads"].append("fir30-bbm0.block4k")
    man["workloads"].append({"name": "fir30-bbm0.block4k",
                             "config": "fir30-bbm0", "traffic": "block4k",
                             "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "fir.flushes", "unit": "flushes",
                             "better": "higher", "source": "host_clock",
                             "layer": "serve.FilterbankEngine",
                             "moves": "samples_per_s"})
    cell = mf.resolve(man, "fir30-bbm0.block4k", b)
    assert cell.traffic["samples"] == 4096
    assert "samples_per_s" in {m["name"] for m in cell.end_to_end}
    assert "fir.flushes" in {m["name"] for m in cell.per_layer}

    class R:
        counters = {"flushes": 7}
    assert cell.reader("fir.flushes").read(R) == 7
    # the metric without a workloads key reaches every cell reporting
    # samples_per_s, and no LM cell
    lm = mf.resolve(man, "qwen2-0.5b-bbm0.chat", b)
    assert "fir.flushes" not in {m["name"] for m in lm.per_layer}
