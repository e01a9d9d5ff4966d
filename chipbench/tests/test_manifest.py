"""``BENCHMARK.json`` and the files it names: allowed names and units,
every ``moves`` an end-to-end metric that each listed cell reports, one
file per config / mix / metric, and a new cell found without an edit."""

import json
import os
import re
import shutil

import pytest

from chipbench import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAN = H.manifest()


def cells_reporting(metric):
    return [w["name"] for w in MAN["workloads"]
            if "workloads" not in metric or w["name"] in metric["workloads"]]


def test_names_and_units():
    rows = MAN["configs"] + MAN["workloads"] + MAN["end_to_end"] \
        + MAN["per_layer"]
    for row in rows:
        assert NAME.match(row["name"]), row["name"]
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    names = [r["name"] for r in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_moves_is_reported_by_every_listed_cell():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in cells_reporting(m):
            assert cell in cells_reporting(e2e[m["moves"]]), (m["name"],
                                                              cell)
    for w in MAN["workloads"]:
        mine = [m for m in MAN["end_to_end"]
                if w["name"] in cells_reporting(m)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(w["name"] in cells_reporting(m)
                   for m in MAN["per_layer"])


def test_every_entry_has_its_files_and_they_agree():
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        spec = H.load_json(H.named_file("metrics", m["name"], ".json"))
        # which cells report it is the manifest's to say: a later PR
        # adds its cell there and edits no metric's file
        assert "workloads" not in spec
        for k, v in m.items():
            assert k == "workloads" or spec[k] == v, (m["name"], k)
        assert hasattr(H.load_module("readers", spec["reader"]), "read")
    for w in MAN["workloads"]:
        cell = H.Cell(MAN, w["name"])
        assert cell.traffic["driver"] and cell.limits
        for kind in ("reference", "systems", "flops"):
            H.named_file(kind, cell.config_name, ".py")
        H.named_file("drivers", cell.traffic["driver"], ".py")
    for c in MAN["configs"]:
        conf = H.load_json(os.path.join(H.ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert conf["source"].startswith(c["source"][:40])


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A config, a mix, a metric and a reader added as new files (and
    entries) are found by name; no file that was there is edited."""
    root = tmp_path
    here = root / "chipbench"
    for kind in ("configs", "traffic", "limits", "metrics", "readers"):
        os.makedirs(here / kind)
    shutil.copy(os.path.join(H.HERE, "peaks.json"), here / "peaks.json")
    (here / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "source": "test", "sizes": {}, "reduced": []}))
    (here / "traffic" / "mix_x.json").write_text(json.dumps(
        {"driver": "train", "batch": 4}))
    (here / "limits" / "tiny_x.json").write_text(json.dumps({"a": 1.0}))
    (here / "metrics" / "answer.json").write_text(json.dumps(
        {"name": "answer", "unit": "count", "reader": "fortytwo",
         "args": {"plus": 1}}))
    (here / "readers" / "fortytwo.py").write_text(
        "def read(run, plus):\n    return run['base'] + plus\n")
    (here / "metrics" / "silent.json").write_text(json.dumps(
        {"name": "silent", "unit": "%", "reader": "nothing"}))
    (here / "readers" / "nothing.py").write_text(
        "def read(run):\n    return None\n")
    man = {"paths": ["chipbench"],
           "configs": [{"name": "tiny",
                        "file": "chipbench/configs/tiny.json"}],
           "workloads": [{"name": "tiny_x", "config": "tiny",
                          "traffic": "mix_x", "chips": 1}],
           "per_layer": [{"name": "answer", "unit": "count"}]}
    cell = H.Cell(man, "tiny_x", root=str(root))
    assert cell.traffic["batch"] == 4 and cell.limits == {"a": 1.0}
    got = H.read_metrics(man["per_layer"], {"base": 41}, cell,
                         here=str(here))
    assert got == {"answer": {"value": 42.0, "unit": "count"}}
    # a reader that reads nothing fails the run by the metric's name
    with pytest.raises(H.BenchError, match="silent"):
        H.read_metrics([{"name": "silent", "unit": "%"}], {}, cell,
                       here=str(here))


def test_decide_needs_every_number_within_its_limit():
    ok = {"a": {"value": 0.1, "limit": 0.2}}
    assert H.decide(ok)
    assert not H.decide({**ok, "b": {"value": 0.3, "limit": 0.2}})
    assert not H.decide({"a": {"value": float("nan"), "limit": 1.0}})
    assert not H.decide({})
