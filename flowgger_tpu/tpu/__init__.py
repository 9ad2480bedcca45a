"""Batched TPU decode tier.

The reference parses each log line with branch-heavy per-line scalar code
(decoder/rfc5424_decoder.rs hot loop, splitter/line_splitter.rs:44-54).
This tier replaces that with columnar, fixed-shape decoding: N lines are
packed into a ``[N, L]`` uint8 tensor and parsed entirely with
data-parallel primitives (cumulative sums for field segmentation,
backslash-run parity + prefix-XOR for quote semantics, ``top_k`` for
k-th-delimiter extraction) that XLA maps onto the TPU's vector units —
no sequential NFA, no data-dependent control flow.

Correctness contract: rows the kernel marks ``ok`` decode *identically*
to the scalar oracle (differential-tested); anything structurally
unusual sets a per-row fallback flag and is re-decoded by the scalar
path, so the pipeline's observable behavior — including per-line error
messages — is byte-identical with the reference's semantics.
"""
