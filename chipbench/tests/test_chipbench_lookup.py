"""A cell is found by name, from files alone: a throwaway benchmark root
gains a configuration, a traffic mix and a per-layer metric by new files
and new entries only, and the harness's lookup finds them."""

import json
import os
import sys
import types

import pytest

from chipbench import cell, run
from chipbench.tests import tiny

REPO = tiny.REPO


def test_the_repository_cells_resolve():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        c = cell.load(REPO, w["name"])
        assert c.reference().layout(c.config)
        assert c.costs().weight_bytes(c.config) > 0
        assert {m.name for m in c.end_to_end} >= {"setup_s"}
        assert len(c.end_to_end) >= 2 and c.per_layer
        for m in c.per_layer:
            assert callable(c.reader(m.name).read)


def test_a_new_cell_and_metric_from_new_files_only(tmp_path):
    root = tiny.make_root(str(tmp_path))
    # a later PR's additions: a config file, a traffic file, a reader
    conf = dict(tiny.DENSE, name="tiny-dense-b", num_hidden_layers=3,
                reduced=[])
    path = os.path.join("chipbench", "configs", "tiny-dense-b.json")
    with open(os.path.join(root, path), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "chipbench", "traffic", "short.json"), "w") as f:
        json.dump(tiny.traffic(0.5), f)
    with open(os.path.join(root, "chipbench", "metrics", "ticks_per_s.py"),
              "w") as f:
        f.write("def read(rec):\n    return len(rec.ticks) / rec.seconds\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-dense-b", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-dense-b.short",
                               "config": "tiny-dense-b", "traffic": "short",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "ticks_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine host tick",
                               "moves": "itl_p90_ms",
                               "workloads": ["tiny-dense-b.short"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    c = cell.load(root, "tiny-dense-b.short")
    assert c.config["num_hidden_layers"] == 3
    assert c.traffic["correct"]["max_logit_gap"] == 0.5
    names = [m.name for m in c.per_layer]
    assert "ticks_per_s" in names
    assert c.reader("ticks_per_s").read(
        type("R", (), {"ticks": [1, 2, 3], "seconds": 1.5})) == 2.0
    # the metric is the new cell's alone
    assert "ticks_per_s" not in [m.name for m in
                                 cell.load(root, "tiny-dense.chat").per_layer]
    with pytest.raises(KeyError, match="no workload"):
        cell.load(root, "no-such-cell")


def test_a_new_kind_of_cell_is_found_by_name(tmp_path, monkeypatch):
    """A traffic file's ``kind`` names the module that runs the cell
    (``chipbench/<kind>.py``), so a new kind is a new file: here one
    held in memory stands in for it."""
    root = tiny.make_root(str(tmp_path))
    seen = []

    def probe_run(c, seed, seconds, trace, t_process, peaks, log, control):
        seen.append((c.name, seed, seconds, control))
        return {"record": None, "setup_s": 2.5, "attempted": 3, "failed": 0,
                "end_to_end": {"itl_p90_ms": 7.0}, "memory": {},
                "compiles_in_window": 0, "breakdown": None, "correct": True,
                "checks": {"probe": {"value": 0.0, "limit": 1.0}}}

    monkeypatch.setitem(sys.modules, "chipbench.probe",
                        types.SimpleNamespace(run=probe_run))
    with open(os.path.join(root, "chipbench", "traffic", "probe.json"), "w") as f:
        json.dump(dict(tiny.traffic(0.5), kind="probe"), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-dense.probe",
                               "config": "tiny-dense", "traffic": "probe",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p90_ms":
            m["workloads"].append("tiny-dense.probe")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    line = run.run_cell(root, "tiny-dense.probe", 2**31 + 5, 4.0, False,
                        require_chip=False, peaks=tiny.PEAKS)
    assert seen == [("tiny-dense.probe", 2**31 + 5, 4.0, False)]
    assert line["correct"] is True and line["attempted"] == 3
    assert line["metrics"]["itl_p90_ms"]["value"] == 7.0
    assert line["metrics"]["setup_s"]["value"] == 2.5
    assert list(line)[-1] == "checks"


def test_the_mamba2_embedding_has_the_published_padded_rows():
    c = cell.load(REPO, "mamba2-chat-burst")
    assert c.config["vocab_size"] == 50277
    assert c.reference().layout(c.config)["embed"].shape == (50288, 1536)
    assert c.reference().program_config(c.config)["vocab_size"] == 50288
