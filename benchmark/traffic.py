"""A traffic mix is a data file; this is the one reader of all of them.

Keys of ``traffic/<mix>.json``:

``judged``           the suffix of the per-layer metrics a cell of this
                     mix reports (``"tput"``: every
                     ``layer_metrics/*.tput.json``)
``corpus``           the lines: a file under ``corpora/`` (``corpus.py``)
``rate_lines_per_s`` ``"max"``: a closed loop, every stream written as
                     fast as its pipe or socket takes it.  (An open
                     loop, on a schedule, comes with the first cell
                     that needs one: PERF.md, Open questions.)
``sources``          the streams the lines arrive on (default 1, at most
                     1024): the one pipe of a stdin deployment, or that
                     many TCP connections to a tcp deployment's
                     listener, all opened before set-up and held to the
                     end, with equal shares: the next chunk goes to
                     whichever stream has nothing queued
``pool_lines``       lines made from the seed and replayed in a cycle
``chunk_lines``      lines stamped and queued at a time
``warm_min_s``       set-up runs the mix at least this long before the
                     window may open, and as long again after the last
                     program that the checkout's cache did not hold was
                     compiled, and then until a slice of it loads and
                     compiles nothing, counts no decline and flows
                     (``run.py`` ``Run.warm_up``): whatever a collector
                     does once after so many batches happens before the
                     window, not in it

Everything below is a pure function of the file and the seed.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULTS = {"pool_lines": 262144, "chunk_lines": 512, "warm_min_s": 0,
            "sources": 1}
MAX_SOURCES = 1024
# lines of one write are stamped a microsecond apart, within this many
SPREAD_US = 1000


def load(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = dict(DEFAULTS, **json.load(f))
    if not (isinstance(mix.get("judged"), str) and mix["judged"].isalnum()):
        raise ValueError(f"traffic {name}: judged names the suffix of the "
                         "per-layer metrics")
    if mix.get("rate_lines_per_s") != "max":
        raise ValueError(f'traffic {name}: rate_lines_per_s must be "max" '
                         "(this generator writes closed loops)")
    n = mix["sources"]
    if not (isinstance(n, int) and not isinstance(n, bool)
            and 1 <= n <= MAX_SOURCES):
        raise ValueError(f"traffic {name}: sources is a whole number from 1 "
                         f"to {MAX_SOURCES}")
    if not os.path.exists(os.path.join(HERE, "corpora",
                                       str(mix.get("corpus")) + ".json")):
        raise ValueError(f"traffic {name}: no corpora/{mix.get('corpus')}.json")
    return mix


def stamps(base_us, n):
    """Due times of the ``n`` lines of one write."""
    return base_us + np.arange(n, dtype=np.int64) % SPREAD_US
