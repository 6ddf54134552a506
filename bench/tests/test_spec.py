"""BENCHMARK.json keeps to the contract, and the harness finds every
configuration, traffic mix and metric by name, new ones included."""

from __future__ import annotations

import hashlib
import json
import re

import pytest
from conftest import ROOT, write_tiny_tree

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(ROOT)


def test_spec_keys_and_names(spec):
    assert set(spec) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads") for x in spec[k]]
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for n in names + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(metrics)) == len(metrics)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len(json.dumps(spec)) < 64 * 1024


def test_every_cell_resolves_and_reports(spec):
    for w in spec["workloads"]:
        cell = harness.resolve_cell(ROOT, spec, w["name"])
        assert cell.config["name"] == w["config"]
        assert "trainer" in cell.mix and "limits" in cell.config
        e2e = harness.metric_specs(spec, w["name"], trace=False)
        per_layer = harness.metric_specs(spec, w["name"], trace=True)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert per_layer
        for m in e2e + per_layer:
            assert callable(harness.load_reader(cell.bench_dir, m["name"]))


def _code_digest(root):
    h = hashlib.sha256()
    for p in sorted((root / "bench").rglob("*.py")):
        h.update(p.read_bytes())
    return h.hexdigest()


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = write_tiny_tree(tmp_path)
    before = _code_digest(root)
    bench = root / "bench"
    cfg = json.loads((bench / "configs/tiny-sage.json").read_text())
    cfg["name"] = "other-sage"
    (bench / "configs/other-sage.json").write_text(json.dumps(cfg))
    mix = {"name": "sync", "buffer": False, "trainer": {"variant": "distdgl", "mode": "sync", "batch_size": 8}}
    (bench / "traffic/sync.json").write_text(json.dumps(mix))
    (bench / "metrics/steps_seen.py").write_text("def read(run):\n    return run.steps\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="other-sage",
                                file="bench/configs/other-sage.json"))
    spec["workloads"].append({"name": "other-sage.sync", "config": "other-sage",
                              "traffic": "sync", "chips": 1, "why": "new"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                              "source": "program_counter", "layer": "run loop",
                              "moves": "seeds_per_s", "workloads": ["other-sage.sync"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = harness.load_spec(root)
    cell = harness.resolve_cell(root, spec, "other-sage.sync")
    assert cell.config["name"] == "other-sage"
    assert cell.mix["trainer"]["mode"] == "sync"
    names = {m["name"] for m in harness.metric_specs(spec, "other-sage.sync", trace=True)}
    assert "steps_seen" in names
    read = harness.load_reader(cell.bench_dir, "steps_seen")
    assert read(type("R", (), {"steps": 5})()) == 5
    assert _code_digest(root) != before  # only the new reader was added
    assert all(
        (root / p).read_bytes() == (ROOT / p).read_bytes()
        for p in ("bench/harness.py", "bench/run.py", "bench/check.py")
    )


def test_unknown_workload_is_an_error(spec):
    with pytest.raises(KeyError):
        harness.resolve_cell(ROOT, spec, "no-such.cell")
