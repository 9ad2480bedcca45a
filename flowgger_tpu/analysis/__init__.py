"""flowcheck: AST-based invariant checker for this repo's own contracts.

Generic linters cannot see the invariants this pipeline's correctness
actually rests on: jitted kernels must stay trace-pure or they silently
recompile off the >=50M lines/sec target, the supervisor/breaker/queue
layer shares mutable state across a dozen threads, and every device
decode/encode route is only *allowed* to exist because a scalar oracle
reproduces its bytes exactly (BASELINE.json / PAPER section 1).
``flowcheck`` encodes those invariants as a rule set over the repo's own
Python AST — the Python tier's counterpart to the ASan/TSan self-checks
the native tier already gets in ci.sh.

Rules (see ``flowcheck --list-rules`` / README "Static analysis"):

- **FC01 trace-safety** — no wall clocks, Python RNG, I/O, host syncs,
  or tracer-dependent Python branching in code reachable from a
  ``jax.jit`` entry point;
- **FC02 thread discipline** — counters mutated from thread targets are
  lock-guarded (or routed through ``utils.metrics``), and no blocking
  call is made while holding a lock;
- **FC03 byte-identity contract** — every ``tpu/device_*`` /
  ``encode_*_block`` module registers its scalar oracle
  (``SCALAR_ORACLE``) and a differential test (``DIFF_TEST``), both
  verified against the tree;
- **FC04 exception hygiene** — no bare/swallowing ``except`` in
  supervised threads, sinks, transports, or the breaker;
- **FC05 config-key drift** — the ``lint.py`` known-key namespace must
  match the ``config.lookup*`` call sites the code actually reads;
- **FC06 metric-name discipline** — every counter/gauge/histogram name
  resolves against the ``utils/metrics.py`` declarations (no typo'd
  silently-dead series);
- **FC07 lock discipline** — no journal emit / sink write / file I/O
  while holding a lock (stage-under-lock, emit-after-release), and the
  per-module lock-acquisition graph stays acyclic;
- **FC08 degradation-event completeness** — every decline/trip/shed
  site reaches a typed ``obs/events.py`` emit with a reason registered
  in the ``REASONS`` vocabulary (and no dead vocabulary);
- **FC09 fault-site coverage** — every ``utils/faultinject.py`` site is
  registered in ``KNOWN_SITES``, documented in the ``flowgger.toml``
  fault catalog, and drilled by a test or ``tools/chaos.py``;
- **FC10 thread/resource lifecycle** — every thread start leaves a
  reachable join path for drain, every instance-state fd/socket has a
  close path.

The package is deliberately dependency-free (``ast`` + stdlib only; no
JAX, no numpy) so ``python -m flowgger_tpu.analysis`` runs in seconds on
any Python >= 3.10 — CI gates on it before the test suite even starts.

Per-line suppressions: ``# flowcheck: disable=FC04 -- reason`` on the
finding's line (or alone on the line above).  Pre-existing findings can
be frozen in a committed baseline (``.flowcheck-baseline.json``,
``--write-baseline``); CI fails only on non-baselined findings.
"""

from .core import Finding, Project, Rule, all_rules, run_check  # noqa: F401

__all__ = ["Finding", "Project", "Rule", "all_rules", "run_check"]
