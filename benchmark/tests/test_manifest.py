"""``BENCHMARK.json`` and the data files it names agree, and every
per-layer metric file names a reader that exists and an end-to-end
metric that every cell of its suffix reports.  Held twice: to the
manifest as it is, and to it with the relay deployment and its cell
entered (``relay.py`` here), so that both configurations' files are
held to the same rules and the PR that admits the cell finds them
sound."""

import importlib
import json
import os
import tomllib

import pytest
import relay

from benchmark import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    AS_IT_IS = json.load(f)
# everything below reads BENCH: the manifest with the relay cell entered
# holds every entry of the manifest as it is, and those two more
BENCH = relay.admitted(AS_IT_IS)
CONFIGS, CELLS = BENCH["configs"], BENCH["workloads"]
FILES = {os.path.splitext(fn)[0]: os.path.join(HERE, "layer_metrics", fn)
         for fn in sorted(os.listdir(os.path.join(HERE, "layer_metrics")))}


def cells_judged(suffix):
    return [w["name"] for w in BENCH["workloads"]
            if traffic.load(w["traffic"])["judged"] == suffix]


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_paths_and_command_stay_inside_the_benchmark():
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]


@pytest.mark.parametrize("name", sorted(FILES))
def test_layer_metric_file(name):
    with open(FILES[name]) as f:
        spec = json.load(f)
    assert set(spec) == {"reader", "args", "layer", "unit", "moves",
                         "better", "source"}
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    suffix = name.rsplit(".", 1)[1]
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == spec["moves"])
    cells = cells_judged(suffix)
    assert cells, f"no cell is judged {suffix}"
    assert all(reports(moved, c) for c in cells)
    # and the manifest says the same, and lists those cells
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    keys = ("layer", "unit", "moves", "better", "source")
    assert {k: entry[k] for k in keys} == {k: spec[k] for k in keys}
    assert sorted(entry["workloads"]) == sorted(cells)


def test_the_manifest_and_the_files_name_the_same_metrics():
    assert sorted(m["name"] for m in BENCH["per_layer"]) == sorted(FILES)


@pytest.mark.parametrize("conf", CONFIGS, ids=lambda c: c["name"])
def test_configuration_files(conf):
    assert conf["file"] == f"benchmark/configs/{conf['name']}.json"
    with open(os.path.join(ROOT, conf["file"])) as f:
        facts = json.load(f)
    assert facts["source"] == conf["source"] and len(conf["source"]) <= 200
    assert facts["reduced"] == conf["reduced"]
    assert facts["guarantees"] and "assumed" in facts
    with open(os.path.join(ROOT, conf["file"][:-5] + ".toml")) as f:
        toml = f.read()
    assert "@SINK@" in toml and "@CACHE@" in toml
    # the way in: the generator's pipe, or its connections to a listener
    # on a port of the harness's choosing
    way_in = tomllib.loads(toml)["input"]["type"]
    assert way_in in ("stdin", "tcp")
    assert ("@LISTEN@" in toml) == (way_in == "tcp")
    mine = [c for c in CELLS if c["config"] == conf["name"]]
    assert mine, "a configuration no cell uses"
    for cell in mine:
        if way_in == "stdin":
            assert traffic.load(cell["traffic"])["sources"] == 1
    # the program's defaults stay: no tier, economics or watchdog key
    assert not [k for k in ("tpu_pallas", "tpu_fuse", "tpu_encode", "tpu_batch",
                            "tpu_flush", "timeout", "economics")
                if k in toml]


@pytest.mark.parametrize("cell", CELLS, ids=lambda w: w["name"])
def test_cells(cell):
    assert cell["config"] in {c["name"] for c in CONFIGS}
    mix = traffic.load(cell["traffic"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    mine = [m["name"] for m in BENCH["end_to_end"]
            if reports(m, cell["name"])]
    assert "setup_s" in mine and len(mine) >= 2
    assert mix["judged"] == "tput"
    assert sorted(mine) == ["lines_per_s", "setup_s"]


def test_entering_the_relay_cell_changes_nothing_else():
    """``relay.admitted`` only appends: two entries and the cell's name
    in the lists; taken out again, the manifest is as it is."""
    bench = json.loads(json.dumps(BENCH))
    assert bench["configs"].pop() == relay.CONFIG
    assert bench["workloads"].pop() == relay.CELL
    for m in bench["end_to_end"] + bench["per_layer"]:
        if relay.CELL["name"] in m.get("workloads", ()):
            assert m["workloads"].pop() == relay.CELL["name"]
    assert bench == AS_IT_IS
    assert relay.CELL["name"] not in {w["name"]
                                      for w in AS_IT_IS["workloads"]}
    assert len({(w["config"], w["traffic"]) for w in CELLS}) == len(CELLS)
