"""Whole runs on the CPU (``--rehearse``: the harness's look for a chip
skipped, everything else as on the chip, at a small size): a sound run
is correct and leaves nothing behind, and a run with the timed path
broken underneath (``faults.py``) comes out ``correct: false``, the
control first.  Slow: some 25 s a run.

The faults a cell of this system can have: an answer altered where it
is produced (``alter``), part of a batch left out (``half``, ``drop``),
an answer given twice (``dup``), the order of one stream broken
(``swap``; with many connections ``reverse``, which breaks some
connection's own order whatever the batching).  It has no training state
and no exchange between chips.
"""

import json
import os
import re
import subprocess
import sys

import pytest
import relay

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORK = os.path.join(ROOT, "benchmark", "work")


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """Where each cell is run from: the admitted one from the repo's
    root, the relay cell from a scratch root whose manifest names it."""
    return {"backfill.drain": ROOT, relay.CELL["name"]: relay.scratch_root(
        tmp_path_factory.mktemp("relay_root"))}


# a rehearsal's window, seconds: with 64 reader threads beside the
# conductor a CPU run of the relay cell can pass two seconds without a
# flush counted, and a ratio over `batches` then finds nothing to read
SECONDS = {"backfill.drain": "2", relay.CELL["name"]: "6"}


def run(root, cell, *more, seed=2**31 + 77):
    before = set(os.listdir(WORK)) if os.path.isdir(WORK) else set()
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds",
         SECONDS[cell], "--rehearse", *more],
        capture_output=True, text=True, timeout=600, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    after = set(os.listdir(WORK)) if os.path.isdir(WORK) else set()
    assert after <= before, "work files left behind"
    return json.loads(p.stdout.splitlines()[-1]), p


GB_ = r"\d+\.\d{3} GB"
MEMORY = re.compile(
    rf"memory: collector {GB_} before the comparison, {GB_} after; "
    rf"generator {GB_}; sink reader {GB_}; comparison children: sum {GB_}, "
    rf"largest {GB_} \(\d+ children, the sink's \d+: sum {GB_}\); "
    rf"sink file {GB_}, \d+ records$")

# the admitted cell, and the one that takes the tcp way in
CELLS = ["backfill.drain", relay.CELL["name"]]


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(roots, cell, trace):
    result, p = run(roots[cell], cell, "--trace", trace)
    # the parent's keys, in its order: the memory line added none
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", *(["breakdown"] if trace == "1"
                                        else []), "compared"]
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes",
        *(["busy_s", "window_s"] if trace == "1" else [])}
    out = p.stdout.splitlines()
    said = [k for k, l in enumerate(out) if "] memory: " in l]
    assert len(said) == 1
    assert MEMORY.search(out[said[0]]), out[said[0]]
    metric_lines = [k for k, l in enumerate(out)
                    if any(f"] {m} = " in l for m in result["metrics"])]
    assert len(metric_lines) == len(result["metrics"])
    assert said[0] < min(metric_lines)
    assert "not read" not in out[said[0]]       # every process's peak
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10_000
    assert result["device"]["platform"] == "cpu"     # named, never a TPU
    assert all(v == 0 and lim == 0 for v, lim in result["compared"].values())
    last = p.stderr.strip().splitlines()[-1]
    assert last.startswith("compared (") and "limit 0" in last
    with open(os.path.join(roots[cell], "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind = "per_layer" if trace == "1" else "end_to_end"
    mine = {m["name"] for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]}
    got = set(result["metrics"])
    # no device plane on the CPU: its two readers find nothing to read
    # and the harness leaves them out
    assert got <= mine
    assert mine - got <= {n for n in mine if n.startswith(
        ("device.idle_share", "kernels.hbm_roofline"))}


FAULTS = {"backfill.drain": ["coarse_ts", "alter", "drop", "half", "dup",
                             "swap"],
          relay.CELL["name"]: ["coarse_ts", "reverse", "alter", "half", "dup"]}


@pytest.mark.parametrize("cell, fault", [(c, f) for c in CELLS
                                         for f in FAULTS[c]])
def test_a_broken_run_is_not_correct(roots, cell, fault):
    result, _ = run(roots[cell], cell, "--trace", "0", "--break", fault)
    assert result["correct"] is False
    assert any(v > lim for v, lim in result["compared"].values())


def test_without_a_tpu_there_is_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "backfill.drain", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert "needs a TPU" in p.stderr
