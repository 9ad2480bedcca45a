#!/usr/bin/env python
"""Compiled-HLO pass census for the rfc5424 kernel: how many fusions
touch a [N, L]-sized operand, and what kind.  The kernel's cost model is
HBM passes over [N, L] planes, so the fusion count with large shapes is
the number to drive down.  Works on whatever backend is active (the TPU
fusion structure is what matters; run under the live chip).

``HLO_PALLAS=1`` switches to the stage-1 structural-pass comparison
(PR 20): the jnp ``structural_index`` screen's [N, L]-touching op count
from its compiled HLO vs the Pallas classifier's count from its
TPU-lowered StableHLO — where the whole screen is ONE fused kernel
(the mosaic custom-call) plus the u8→i32 widen, so the [N, L] plane is
read once instead of re-materialized per fusion.  The same pair of
counts backs the ``bench.py --smoke`` pass-count-reduction gate."""

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


def jnp_stage1_passes(n, length):
    """[N,L]-touching op count for the jnp structural screen (the
    compiled-HLO census on the active backend — each such fusion is
    one HBM round-trip over the byte plane)."""
    from flowgger_tpu.tpu import jsonidx as JI

    b = jnp.zeros((n, length), jnp.uint8)
    ln = jnp.full((n,), length, jnp.int32)
    comp = jax.jit(lambda bb, ll: digest_all(jnp, JI.structural_index(
        bb, ll, max_fields=8, scan_impl="lax", extract_impl="sum",
        nested=4))).lower(b, ln).compile()
    counts, _ = _census_hlo(comp.as_text(), n, length)
    return sum(counts.values()), counts


def pallas_stage1_passes(n, length):
    """[N,L]-materializing op count for the Pallas classifier, from
    its TPU-lowered StableHLO (lowering needs no chip): the mosaic
    custom-call reads the plane once into VMEM, so the only [N,L]
    tensors in the program are the widen feeding it.  Counted
    conservatively — every op whose RESULT is [N,L]-shaped, i.e.
    every time the byte plane materializes."""
    import functools

    from jax import export as jexport

    from flowgger_tpu.tpu import pallas_kernels as PK

    fn = functools.partial(PK.structural_index_pallas, max_fields=8,
                           nested=4, block_rows=min(n, 256),
                           interpret=False)
    spec = (jax.ShapeDtypeStruct((n, length), jnp.uint8),
            jax.ShapeDtypeStruct((n,), jnp.int32))
    exp = jexport.export(jax.jit(fn), platforms=["tpu"])(*spec)
    txt = exp.mlir_module()
    big = f"tensor<{n}x{length}x"
    passes = 0
    for line in txt.splitlines():
        s = line.strip()
        if not re.match(r"%\S+\s*=", s):
            continue
        rhs = s.split("=", 1)[1]
        # result type(s) follow the last "->" (or ":" for unary ops)
        tail = rhs.rsplit("->", 1)[-1] if "->" in rhs else \
            rhs.rsplit(":", 1)[-1]
        if big in tail:
            passes += 1
    return passes


def main():
    if os.environ.get("HLO_PALLAS"):
        n, length = min(N, 4096), L
        jnp_passes, counts = jnp_stage1_passes(n, length)
        pallas_passes = pallas_stage1_passes(n, length)
        print(f"stage-1 structural screen, geometry [{n},{length}]:")
        print(f"  jnp [N,L]-touching passes:    {jnp_passes}")
        for k, v in counts.most_common():
            print(f"    {k:24s} {v}")
        print(f"  pallas [N,L] materializations: {pallas_passes} "
              "(TPU StableHLO; the kernel body is one VMEM pass)")
        ratio = jnp_passes / max(pallas_passes, 1)
        print(f"  pass-count reduction: {ratio:.1f}x")
        return

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
