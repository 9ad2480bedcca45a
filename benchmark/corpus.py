"""The seeded RFC 5424 corpus and the pool the generator replays.

A corpus is a data file, ``corpora/<name>.json``, which a traffic mix
names (``"corpus"``); this is the one generator of all of them.  The
file holds message formats with weights (``messages``: PRI, APP-NAME,
PROCID, MSGID, the text, and for a line quoted whole from a document
its own ``host`` and ``sd``), the structured data that emitters add
(``sd``: weighted lists of elements, each an SD-ID and its pairs), the
default ``host``, a share of ``junk`` lines, and the ``fields`` that
``{name}`` in any of those texts stands for: ``choice`` (of texts,
which may hold fields again), ``int`` (a range, zero-padded to ``pad``),
``ipv4``, ``repeat`` (a text, so many times).  Where each part comes
from is in the file (``sources``, ``assumed``).

``build_pool`` lays the lines out for the generator: one blob, each
line's TIMESTAMP a fixed-width placeholder that the generator overwrites
with the line's due time before it writes it.  No JAX, no import of the
program.
"""

from __future__ import annotations

import bisect
import itertools
import json
import os
import random
import re

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FIELD_RE = re.compile(r"\{([A-Za-z_]+)\}")

# what the generator overwrites: 27 bytes, microseconds, UTC
TS_PLACEHOLDER = b"1970-01-01T00:00:00.000000Z"
TS_WIDTH = len(TS_PLACEHOLDER)


def load(name):
    with open(os.path.join(HERE, "corpora", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


class Lines:
    """Draws lines of one corpus from one ``random.Random``."""

    def __init__(self, table, rng):
        self.table, self.rng = table, rng
        self.messages = self._weighted(table["messages"])
        self.sd = self._weighted(table["sd"])
        self.junk = table["junk"]

    @staticmethod
    def _weighted(entries):
        return entries, list(itertools.accumulate(
            e["weight"] for e in entries))

    def pick(self, weighted):
        entries, upto = weighted
        return entries[bisect.bisect_right(
            upto, self.rng.random() * upto[-1])]

    def field(self, m):
        f, rng = self.table["fields"][m.group(1)], self.rng
        if "choice" in f:
            return self.fill(rng.choice(f["choice"]))
        if "int" in f:
            return "%0*d" % (f.get("pad", 1), rng.randint(*f["int"]))
        if "ipv4" in f:
            return ".".join(str(rng.randrange(1, 255)) for _ in range(4))
        text, lo, hi = f["repeat"]
        return text * rng.randint(lo, hi)

    def fill(self, text):
        return FIELD_RE.sub(self.field, text)

    def structured_data(self):
        elements = self.pick(self.sd)["elements"]
        if not elements:
            return "-"
        return "".join(
            "[" + " ".join([sid] + [f'{k}="{self.fill(v)}"'
                                    for k, v in pairs]) + "]"
            for sid, pairs in elements)

    def line(self):
        """One line (bytes, no terminator), its TIMESTAMP the
        placeholder; the junk line as it stands."""
        if self.rng.random() * 10000 < self.junk["per_10000"]:
            return self.junk["text"].encode("utf-8")
        m = self.pick(self.messages)
        sd = m["sd"] if "sd" in m else self.structured_data()
        text = self.fill(m["text"])
        return (f"<{m['pri']}>1 {TS_PLACEHOLDER.decode()} "
                f"{self.fill(m.get('host', self.table['host']))} {m['app']} "
                f"{self.fill(m['procid'])} {m['msgid']} {sd}"
                + (" " + text if text else "")).encode("utf-8")


class Pool:
    """``n`` lines in one blob, newline-terminated.  ``line_off[i]`` is
    where line ``i`` starts (``line_off[n]`` the blob's length),
    ``ts_off[i]`` where its TIMESTAMP placeholder starts, -1 for a line
    that has none (junk)."""

    def __init__(self, blob, line_off, ts_off):
        self.blob = blob
        self.line_off = line_off
        self.ts_off = ts_off
        self.n = len(ts_off)

    def line(self, i, due_us=None):
        """Line ``i`` without its terminator, stamped if asked."""
        raw = bytearray(self.blob[self.line_off[i]:self.line_off[i + 1] - 1])
        if due_us is not None and self.ts_off[i] >= 0:
            at = int(self.ts_off[i] - self.line_off[i])
            raw[at:at + TS_WIDTH] = stamp_text(int(due_us))
        return bytes(raw)


def save_pool(pool, path):
    np.savez(path, blob=np.frombuffer(pool.blob, np.uint8),
             line_off=pool.line_off, ts_off=pool.ts_off)


def load_pool(path):
    z = np.load(path)
    return Pool(z["blob"].tobytes(), z["line_off"], z["ts_off"])


def build_pool(seed, n_lines, corpus):
    """The pool of a run: a pure function of its arguments (``corpus``
    the name of a file under ``corpora/``)."""
    lines = Lines(load(corpus), random.Random(seed))
    parts, line_off, ts_off, at = [], [0], [], 0
    for _ in range(n_lines):
        line = lines.line()
        ts = line.find(TS_PLACEHOLDER, 0, 8 + TS_WIDTH)
        ts_off.append(at + ts if ts > 0 else -1)
        parts.append(line)
        at += len(line) + 1
        line_off.append(at)
    return Pool(b"\n".join(parts) + b"\n",
                np.asarray(line_off, np.int64), np.asarray(ts_off, np.int64))


def stamp_text(due_us):
    """Microseconds since the epoch as the 27 bytes a source writes."""
    import time

    secs, frac = divmod(due_us, 1_000_000)
    return (time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(secs))
            + ".%06dZ" % frac).encode("ascii")


_COLS = np.arange(TS_WIDTH, dtype=np.int64)
_POW = 10 ** np.arange(5, -1, -1, dtype=np.int64)


def stamp_block(pool, a, b, due_us):
    """Lines ``a..b`` of the pool as one bytes object, each stamped with
    its entry of ``due_us`` (int64, microseconds)."""
    base = int(pool.line_off[a])
    buf = np.frombuffer(pool.blob, np.uint8,
                        int(pool.line_off[b]) - base, base).copy()
    at = pool.ts_off[a:b]
    have = at >= 0
    if not have.all():
        at, due_us = at[have], due_us[have]
    if len(at):
        secs = due_us // 1_000_000
        text = np.empty((len(at), TS_WIDTH), np.uint8)
        for s in np.unique(secs):
            text[secs == s] = np.frombuffer(stamp_text(int(s) * 1_000_000),
                                            np.uint8)
        text[:, 20:26] = ((due_us % 1_000_000)[:, None] // _POW) % 10 + 48
        buf[(at - base)[:, None] + _COLS] = text
    return buf.tobytes()
