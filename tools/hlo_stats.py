#!/usr/bin/env python
"""Compiled-HLO pass census for the rfc5424 kernel: how many fusions
touch a [N, L]-sized operand, and what kind.  The kernel's cost model is
HBM passes over [N, L] planes, so the fusion count with large shapes is
the number to drive down.  Works on whatever backend is active (the TPU
fusion structure is what matters; run under the live chip)."""

import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import digest_all
from flowgger_tpu.tpu import rfc5424 as R

N = int(os.environ.get("HLO_N", 65_536))
L = 256
FMT = os.environ.get("HLO_FMT", "rfc5424")


def _decode_fn():
    """The lowered function for HLO_FMT (rfc5424 default; ltsv, gelf,
    rfc3164 for the other kernels' censuses)."""
    if FMT == "ltsv":
        from flowgger_tpu.tpu import ltsv

        return lambda b, ln: digest_all(jnp, ltsv.decode_ltsv(b, ln))
    if FMT == "gelf":
        from flowgger_tpu.tpu import gelf

        return lambda b, ln: digest_all(jnp, gelf.decode_gelf(b, ln))
    if FMT == "rfc3164":
        from flowgger_tpu.tpu import rfc3164

        return lambda b, ln: digest_all(
            jnp, rfc3164.decode_rfc3164(b, ln, jnp.int32(2026)))
    return lambda b, ln: digest_all(jnp, R.decode_rfc5424(b, ln))


def _census_hlo(txt, N, L):
    """[N,L]-touching op counter over a compiled-HLO dump."""
    big = f"{N},{L}"
    counts = collections.Counter()
    fusion_lines = []
    for line in txt.splitlines():
        s = line.strip()
        m = re.match(r"%?([\w.-]+)\s*=\s*(\w+)\[([\d,]*)\]", s)
        if not m:
            continue
        shape = m.group(3)
        op = s.split("=", 1)[1].strip().split("(")[0].split()[-1]
        if "fusion" in s and big in s:
            kind = "loop"
            km = re.search(r'kind=(\w+)', s)
            if km:
                kind = km.group(1)
            counts[f"fusion:{kind}"] += 1
            fusion_lines.append(s[:160])
        elif big in shape and any(
                k in s for k in (" dot(", " dot-general(",
                                 " cumsum", " sort(", " scatter(",
                                 " reduce-window(")):
            counts[op] += 1
    return counts, fusion_lines


def main():
    b = jnp.zeros((N, L), jnp.uint8)
    ln = jnp.full((N,), L, jnp.int32)

    comp = jax.jit(_decode_fn()).lower(b, ln).compile()
    counts, fusion_lines = _census_hlo(comp.as_text(), N, L)
    print(f"{FMT} geometry [{N},{L}] — ops materializing a [N,L] operand:")
    for k, v in counts.most_common():
        print(f"  {k:24s} {v}")
    print(f"\ntotal fusions touching [N,L]: "
          f"{sum(v for k, v in counts.items() if k.startswith('fusion'))}")
    if os.environ.get("HLO_VERBOSE"):
        for fl in fusion_lines:
            print(fl)


if __name__ == "__main__":
    main()
