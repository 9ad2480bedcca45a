"""The relay deployment and its first cell, as ``BENCHMARK.json`` would
name them.  ``relay.fleet_catchup`` runs and is correct on the chip and
is not admitted (PERF.md, PR 29: its ``lines_per_s`` spreads 81% over
51 s runs), so no code of the harness knows these entries: the tests
enter them into the manifest of a scratch root, the way the PR that
admits the cell will enter them into the real one, and rehearse the tcp
way in from there."""

import copy
import json
import os

from benchmark import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONFIG = {
    "name": "relay_tcp_gelf",
    "source": ('flowgger 0.3.x flowgger.toml [input] type="tcp", '
               'format="rfc5424", framing="line" (LF, RFC 6587 s3.4.2), '
               '[output] format="gelf"; lines as backfill_stdin_gelf '
               '(RFC 5424, Loghub arXiv:2008.06448)'),
    "file": "benchmark/configs/relay_tcp_gelf.json",
    "reduced": [],
    "why": ("one collector process on one chip; one TCP listener, a reader "
            "thread per connection, one shared batch handler; batches of "
            "16,384 lines x 512 B, 2 in flight, 50 ms flush (the program's "
            "defaults)"),
}
CELL = {
    "name": "relay.fleet_catchup",
    "config": "relay_tcp_gelf",
    "traffic": "fleet_catchup",
    "chips": 1,
    "why": ("64 closed-loop TCP senders flat out: 64 reader threads and "
            "sessions before one handler, which after two flush walks serves "
            "one connection at a time; between probe stalls it reads as "
            "backfill.drain"),
}


def admitted(bench):
    """``bench`` with the configuration and the cell entered, as
    ``README.md`` says a cell is added: two entries, and the cell's name
    appended to the list of each metric it reports."""
    bench = copy.deepcopy(bench)
    bench["configs"].append(CONFIG)
    bench["workloads"].append(CELL)
    suffix = "." + traffic.load(CELL["traffic"])["judged"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "lines_per_s" or m["name"].endswith(suffix):
            m["workloads"].append(CELL["name"])
    return bench


def scratch_root(folder):
    """A root whose ``BENCHMARK.json`` names the cell, over the same
    tree: ``run.py`` finds its root by its own path as it was started,
    so the tree's directories are links."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = admitted(json.load(f))
    with open(os.path.join(folder, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    for name in ("benchmark", "flowgger_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(folder, name))
    return str(folder)
