"""The plain reference: RFC 5424 line in, GELF 1.1 record out
(``gelf(line)``; ``refchunk.py`` runs it in CPU-only children).

A straightforward scalar implementation of what the two configurations
promise, written for the benchmark and importing nothing of the program
(no JAX either): it is the yardstick, so a later PR cannot move it.  It
follows upstream flowgger 0.3.x (``rfc5424_decoder.rs``,
``gelf_encoder.rs``):

- the line must be UTF-8 and start with ``<`` (or a BOM); the header is
  the first six space-separated fields, ``<PRI>1 TS HOST APP PROCID
  MSGID``; PRI is 0..255; TS is RFC 3339 and becomes seconds since the
  epoch as a double, ``(seconds * 10**9 + nanos) / 1e9``;
- structured data is ``-`` or ``[id k="v" ...]`` blocks; a value
  unescapes ``\\"``, ``\\\\`` and ``\\]``; every pair becomes the
  top-level field ``_k``, ``sd_id`` is the last block's id;
- the record is one JSON object, keys sorted, no spaces: ``version``
  "1.1", ``host`` (``unknown`` if empty), ``short_message`` (the
  trimmed message, ``-`` if none), ``timestamp`` (shortest digits that
  round-trip), ``level`` (PRI & 7), ``full_message`` (the line, right
  trimmed), ``application_name``, ``process_id``;
- a line that breaks any of this is dropped.
"""

from __future__ import annotations

import calendar
import re
from json.encoder import encode_basestring as quote

TS_RE = re.compile(
    r"(\d{4})-(\d\d)-(\d\d)[Tt](\d\d):(\d\d):(\d\d)(?:\.(\d{1,9}))?"
    r"(?:[Zz]|([+-])(\d\d):(\d\d))\Z", re.ASCII)
NAME_BAD = set(' "=]')


def unix_seconds(text):
    m = TS_RE.match(text)
    if not m:
        return None
    y, mo, d, h, mi, s = (int(x) for x in m.group(1, 2, 3, 4, 5, 6))
    if not (1 <= mo <= 12 and 1 <= d <= calendar.monthrange(y, mo)[1]
            and h <= 23 and mi <= 59 and s <= 59):
        return None
    frac = m.group(7)
    nanos = int(frac) * 10 ** (9 - len(frac)) if frac else 0
    off = 0
    if m.group(8):
        oh, om = int(m.group(9)), int(m.group(10))
        if oh > 23 or om > 59:
            return None
        off = (oh * 3600 + om * 60) * (1 if m.group(8) == "+" else -1)
    total = calendar.timegm((y, mo, d, h, mi, s)) - off
    return (total * 1_000_000_000 + nanos) / 1e9


def unescape(v):
    if "\\" not in v:
        return v
    out, i = [], 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v) and v[i + 1] in '"\\]':
            out.append(v[i + 1])
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def sd_block(text, fields):
    """``text`` follows ``[id ``: take its pairs, return the index past
    the closing ``]`` (None: malformed)."""
    i, n = 0, len(text)
    while i < n:
        c = text[i]
        if c == "]":
            return i + 1
        if c in ' "':
            i += 1
            continue
        eq = i
        while eq < n and text[eq] != "=":
            if not 33 <= ord(text[eq]) <= 126 or text[eq] in NAME_BAD:
                return None
            eq += 1
        if eq + 1 >= n or text[eq + 1] != '"':
            return None
        j = eq + 2
        while j < n and text[j] != '"':
            j += 2 if text[j] == "\\" else 1
        if j >= n:
            return None
        fields["_" + text[i:eq]] = unescape(text[eq + 2:j])
        i = j + 1
    return None


def gelf(raw):
    """One line (bytes, no terminator) to its record, or None."""
    try:
        line = raw.decode("utf-8")
    except UnicodeDecodeError:
        return None
    if line.startswith("﻿"):
        line = line[1:]
    elif not line.startswith("<"):
        return None
    f = line.split(" ", 6)
    if len(f) < 7:
        return None
    pri, close = f[0][1:].partition(">")[::2]
    if not (pri.isascii() and pri.isdigit() and int(pri) <= 255
            and close == "1" and ">" in f[0]):
        return None
    ts = unix_seconds(f[1])
    if ts is None:
        return None
    fields = {}
    data = f[6]
    if data.startswith("-"):
        msg = data[1:]
    elif data.startswith("["):
        while True:
            sid, sp, rest = data[1:].partition(" ")
            if not sp:
                return None
            end = sd_block(rest, fields)
            if end is None or end >= len(rest):
                return None
            fields["sd_id"] = sid
            data = rest[end:]
            if data[0] == " ":
                msg = data
                break
            if data[0] != "[":
                return None
    else:
        return None
    msg = msg.strip()
    fields.update(
        version="1.1", host=f[2] or "unknown", short_message=msg or "-",
        timestamp=ts, level=int(pri) & 7, full_message=line.rstrip(),
        application_name=f[3], process_id=f[4])
    return ("{" + ",".join(
        quote(k) + ":" + (quote(v) if isinstance(v, str) else repr(v))
        for k, v in sorted(fields.items())) + "}").encode("utf-8")
