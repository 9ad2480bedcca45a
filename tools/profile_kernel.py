#!/usr/bin/env python
"""Primitive-cost ablation for the rfc5424 device kernel.

Times, with the same chained-fori methodology bench.py uses (so
per-call dispatch costs are excluded), the building blocks the kernel is
made of — on the same [N, L] geometry as the 1M-line bench batch:

- jnp.cumsum int32 / int16 over axis 1
- lax.cummax int32
- one masked-sum reduction pass (the packed field-sum shape)
- one elementwise compare plane (bb == k)
- the full decode_rfc5424

Multiplying the unit costs out against the measured full-kernel time
tells us which family dominates and what the ceiling of a rework is
(this is how the round-2 7-scan kernel was diagnosed as scan-bound and
folded down to 3 scan channels).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

N = 1_000_000
L = 256
CHAIN = 8


def timed(name, fn, *args, chain=None, width=46, unit="ms"):
    """fn must return a scalar-reducible array; chained via xor bit.
    Shared by every tools/profile_*.py harness so the methodology can
    only change in one place."""
    chain = chain or CHAIN

    def chained(a0, *rest):
        def body(i, carry):
            out = fn(jnp.bitwise_xor(a0, (carry % 2).astype(a0.dtype)), *rest)
            return carry + (out.sum().astype(jnp.int32) & 1)

        return jax.lax.fori_loop(0, chain, body, jnp.int32(0))

    jf = jax.jit(chained)
    int(jf(*args))
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        int(jf(*args))
        dt = (time.perf_counter() - t0) / chain
        best = dt if best is None else min(best, dt)
    print(f"{name:{width}s} {best * 1e3:8.2f} {unit}", file=sys.stderr)
    return best


def main():
    dev = jax.devices()[0]
    print(f"device: {dev}  geometry: [{N}, {L}]", file=sys.stderr)
    rng = np.random.default_rng(0)
    bytes_np = rng.integers(32, 127, size=(N, L), dtype=np.uint8)
    b_u8 = jax.device_put(jnp.asarray(bytes_np), dev)
    b_i16 = jax.device_put(jnp.asarray(bytes_np.astype(np.int16)), dev)
    b_i32 = jax.device_put(jnp.asarray(bytes_np.astype(np.int32)), dev)
    lens = jax.device_put(jnp.full((N,), L, jnp.int32), dev)

    timed("elementwise compare u8 -> bool.sum", lambda b: (b == 32), b_u8)
    timed("cumsum i32 (lax)", lambda b: jnp.cumsum(b, axis=1), b_i32)
    timed("cumsum i16 (lax)", lambda b: jnp.cumsum(b, axis=1), b_i16)
    timed("cumsum of mask i32 (where+cumsum)",
          lambda b: jnp.cumsum((b == 32).astype(jnp.int32), axis=1), b_u8)
    timed("cummax i32 (lax)", lambda b: jax.lax.cummax(b, axis=1), b_i32)
    timed("cumsum u8 wraparound (lax)",
          lambda b: jnp.cumsum(b, axis=1, dtype=jnp.uint8), b_u8)
    bT_i32 = jax.device_put(jnp.asarray(bytes_np.astype(np.int32).T), dev)
    timed("cumsum i32 axis0 of [L, N]",
          lambda b: jnp.cumsum(b, axis=0), bT_i32)
    timed("packed 3-channel cumsum i32 (where<<k)",
          lambda b: jnp.cumsum(
              (b == 32).astype(jnp.int32)
              + ((b == 61).astype(jnp.int32) << 10)
              + ((b == 93).astype(jnp.int32) << 20), axis=1), b_u8)
    timed("assoc_scan custom (add|last) pair",
          lambda b: jax.lax.associative_scan(
              lambda x, y: (x[0] + y[0], jnp.maximum(x[1], y[1])),
              ((b == 32).astype(jnp.int32),
               jnp.where(b == 92, 0,
                         jax.lax.broadcasted_iota(jnp.int32, b.shape, 1))),
              axis=1)[0], b_u8)
    timed("one masked-sum reduction (field-sum)",
          lambda b: jnp.sum(jnp.where(b == 32, jnp.int32(7), 0), axis=1),
          b_u8)
    timed("three masked-sum reductions",
          lambda b: (
              jnp.sum(jnp.where(b == 32, jnp.int32(7), 0), axis=1)
              + jnp.sum(jnp.where(b == 61, jnp.int32(5), 0), axis=1)
              + jnp.sum(jnp.where(b == 93, jnp.int32(3), 0), axis=1)),
          b_u8)

    from flowgger_tpu.tpu import rfc5424

    def full_decode(b, ln):
        r = rfc5424.decode_rfc5424(b, ln)
        return r["pair_count"] + r["days"] * 0

    timed("full decode_rfc5424", full_decode, b_u8, lens)


if __name__ == "__main__":
    main()
