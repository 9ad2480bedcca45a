"""Compile the main path's programs for one v5e chip, without the chip.

The TPU's compiler is installed beside JAX and compiles for a chip that
is described, not attached (``jax.experimental.topologies``).  These
tests hand it the programs the served rfc5424 -> GELF path dispatches,
at the production default batch ``[16384, 512]``, with the statics
``FusedRoute.make_kernel`` and ``framing.device_frame_region`` pass on
a TPU.  A pass says the chip's compiler accepts the program; it is not
a chip run and gives no time.

The six Pallas kernels of ``tpu/pallas_kernels.py`` are strict xfails:
Mosaic (jax 0.9.0 / libtpu 0.0.34) refuses each of them, with one of
the two messages quoted below.  A PR that repairs one will see its case
XPASS and fail the suite: drop the mark then.

Layout rules (on-chip-measurement guide, section 2): the topology is
described inside a module-scoped fixture, which skips where it cannot
be described; nothing touches it at import, in ``conftest.py`` or in a
``parametrize`` argument; every compile runs in the test's own process
with the persistent compile cache switched off around it (an entry
written for a described chip cannot be read back without one).  All the
cases live in this one file, so one xdist worker loads libtpu.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

ROWS, MAX_LEN = 16384, 512          # tpu/batch.py DEFAULT_BATCH_SIZE x DEFAULT_MAX_LINE_LEN
REGION = 4 << 20                    # tpu/batch.py _RAW_REGION_CAP: one flush's raw bytes

READ1 = "cannot statically prove that index in dimension 1 is a multiple of 128"
TRUNCI = "Unsupported target bitwidth for truncation"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    """``spec(dtype, *shape)``: a shape on the described chip."""
    import jax

    return lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                      sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """An executable compiled for a described chip is written to the
    persistent cache but cannot be read back without a chip: keep these
    compiles out of it."""
    import jax
    from jax._src import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiles(jitted, *args, **statics):
    compiled = jitted.lower(*args, **statics).compile()
    assert compiled.memory_analysis() is not None
    return compiled


# -- the jnp tiers the default configuration serves: these must compile ------

def test_decode_rfc5424_jit_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu.rfc5424 import decode_rfc5424_jit

    _compiles(decode_rfc5424_jit, spec(jnp.uint8, ROWS, MAX_LEN),
              spec(jnp.int32, ROWS))


@pytest.mark.parametrize("assemble", [False, True],
                         ids=["probe", "assemble"])
def test_fused_rfc5424_gelf_compiles_for_v5e(spec, assemble):
    """The default fused program: jnp decode leg (``pallas="off"`` is
    what ``fused_leg_mode()`` gives with no ``input.tpu_pallas`` key),
    MXU scans, the GELF route's demand mask, nul framing."""
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, fused_routes, pallas_kernels
    from flowgger_tpu.tpu.device_common import TS_W
    from flowgger_tpu.tpu.rfc5424 import DEFAULT_MAX_SD

    assert pallas_kernels.fused_leg_mode() == "off"
    # the probe uploads no timestamp text; the assemble the rendered
    # [N, TS_W] block (device_common.fetch_encode_driver)
    ts_w = TS_W if assemble else 0
    _compiles(
        fused_routes._fused_rfc5424_gelf,
        spec(jnp.uint8, ROWS, MAX_LEN), spec(jnp.int32, ROWS),
        spec(jnp.uint8, ROWS, ts_w), spec(jnp.int32, ROWS),
        max_sd=DEFAULT_MAX_SD, suffix=b"\0",
        impl=aot._scan_impl_for("tpu"), assemble=assemble, extras=(),
        demand=fused_routes.DEMAND["rfc5424_gelf"],
        pallas=pallas_kernels.fused_leg_mode())


def test_frame_sep_spans_jit_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, framing

    _compiles(framing.frame_sep_spans_jit, spec(jnp.uint8, REGION),
              spec(jnp.int32), **aot.framing_statics("line", ROWS, REGION))


def test_frame_gather_jit_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, framing

    _compiles(framing.frame_gather_jit, spec(jnp.uint8, REGION),
              spec(jnp.int32, ROWS), spec(jnp.int32, ROWS),
              **aot.framing_statics("gather", MAX_LEN, REGION))


# -- the Pallas tier: refused by Mosaic, one strict xfail per kernel ---------

def _refused(msg):
    return pytest.mark.xfail(
        strict=True,
        reason=f"Mosaic refuses this kernel: {msg!r} (ROADMAP D2: repair "
               "or delete; drop this mark when the kernel compiles)")


@_refused(READ1)
def test_frame_sep_spans_pallas_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, pallas_kernels

    B = 1 << 16
    _compiles(pallas_kernels.frame_sep_spans_pallas, spec(jnp.uint8, B),
              spec(jnp.int32), **aot.pallas_statics("line", 256, B))


@_refused(READ1)
def test_frame_syslen_spans_pallas_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, pallas_kernels

    B = 1 << 16
    _compiles(pallas_kernels.frame_syslen_spans_pallas,
              spec(jnp.uint8, B), spec(jnp.int32),
              **aot.pallas_statics("syslen", 256, B))


@_refused(READ1)
def test_frame_gather_pallas_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, pallas_kernels

    B = 1 << 20
    _compiles(pallas_kernels.frame_gather_pallas, spec(jnp.uint8, B),
              spec(jnp.int32, 4096), spec(jnp.int32, 4096),
              **aot.pallas_statics("gather", MAX_LEN, B))


@_refused(TRUNCI)
def test_decode_jsonl_pallas_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import pallas_kernels

    _compiles(pallas_kernels.decode_jsonl_pallas,
              spec(jnp.uint8, 4096, 256), spec(jnp.int32, 4096))


@_refused(TRUNCI)
def test_structural_index_pallas_compiles_for_v5e(spec):
    import jax
    import jax.numpy as jnp

    from flowgger_tpu.tpu import pallas_kernels
    from flowgger_tpu.tpu.jsonl import DEFAULT_MAX_FIELDS

    fn = jax.jit(lambda b, ln: pallas_kernels.structural_index_pallas(
        b, ln, DEFAULT_MAX_FIELDS))
    _compiles(fn, spec(jnp.uint8, 4096, 256), spec(jnp.int32, 4096))


@_refused(TRUNCI)
def test_decode_rfc5424_pallas_compiles_for_v5e(spec):
    import jax
    import jax.numpy as jnp

    from flowgger_tpu.tpu.rfc5424 import decode_rfc5424_pallas

    _compiles(jax.jit(decode_rfc5424_pallas),
              spec(jnp.uint8, 4096, 256), spec(jnp.int32, 4096))
