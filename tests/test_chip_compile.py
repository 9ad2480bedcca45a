"""Compile the main path's programs for one v5e chip, without the chip.

The TPU's compiler is installed beside JAX and compiles for a chip that
is described, not attached (``jax.experimental.topologies``).  These
tests hand it the programs the served rfc5424 -> GELF path dispatches,
at the production default batch ``[16384, 512]``, with the statics
``FusedRoute.make_kernel`` and ``framing.device_frame_region`` pass on
a TPU.  A pass says the chip's compiler accepts the program; it is not
a chip run and gives no time.

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


@pytest.fixture(autouse=True)
def _scans_as_on_a_tpu(monkeypatch):
    """The decoders pick their scan lowering from ``jax.default_backend()``,
    which is the CPU here: give them what they pick on the chip."""
    from flowgger_tpu.tpu import aot

    monkeypatch.setattr(aot, "_scan_impl_for", lambda platform: "mm")


def _compiles(jitted, *args, **statics):
    compiled = jitted.lower(*args, **statics).compile()
    assert compiled.memory_analysis() is not None
    return compiled


# -- the programs the default configuration serves: these must compile ------

def test_decode_rfc5424_jit_compiles_for_v5e(spec):
    import jax.numpy as jnp

    from flowgger_tpu.tpu.rfc5424 import decode_rfc5424_jit

    args = spec(jnp.uint8, ROWS, MAX_LEN), spec(jnp.int32, ROWS)
    # the scans are the MXU's, as on the chip, not the CPU's cumsum
    assert "dot_general" in decode_rfc5424_jit.lower(*args).as_text()
    _compiles(decode_rfc5424_jit, *args)


def test_decode_rfc5424_jit_rescue_tier_compiles_for_v5e(spec):
    """The pair rescue's second round: 16 pairs over the smallest bucket."""
    import jax.numpy as jnp

    from flowgger_tpu.tpu.rfc5424 import RESCUE_MAX_PAIRS, decode_rfc5424_jit

    _compiles(decode_rfc5424_jit, spec(jnp.uint8, 256, MAX_LEN),
              spec(jnp.int32, 256), max_pairs=RESCUE_MAX_PAIRS)


@pytest.mark.parametrize("assemble", [False, True],
                         ids=["probe", "assemble"])
def test_split_gelf_encode_kernel_compiles_for_v5e(spec, assemble):
    """The split device encoder the economics probe, over the decode
    program's channels, and for the assembled rows its compaction."""
    import jax
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, device_common, device_gelf
    from flowgger_tpu.tpu.rfc5424 import DEFAULT_MAX_SD, decode_rfc5424_jit

    b, ln = spec(jnp.uint8, ROWS, MAX_LEN), spec(jnp.int32, ROWS)
    dec = {k: spec(v.dtype, *v.shape)
           for k, v in jax.eval_shape(decode_rfc5424_jit, b, ln).items()}
    ts = spec(jnp.uint8, ROWS, device_common.TS_W if assemble else 0)
    statics = dict(suffix=b"\0", max_sd=DEFAULT_MAX_SD,
                   impl=aot._scan_impl_for("tpu"), assemble=assemble,
                   extras=(), elide=True)
    _compiles(device_gelf._encode_kernel, b, ln, dec, ts, ln, **statics)
    if assemble:
        acc, out_len = jax.eval_shape(
            lambda *a: device_gelf._encode_kernel(*a, **statics),
            b, ln, dec, ts, ln)[:2]
        _compiles(device_common._compact_kernel,
                  spec(acc.dtype, *acc.shape),
                  spec(out_len.dtype, *out_len.shape), spec(jnp.bool_, ROWS))


@pytest.mark.parametrize("assemble", [False, True],
                         ids=["probe", "assemble"])
def test_fused_rfc5424_gelf_compiles_for_v5e(spec, assemble):
    """The default fused program: MXU scans, the GELF route's demand
    mask, nul framing."""
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, fused_routes
    from flowgger_tpu.tpu.device_common import TS_W
    from flowgger_tpu.tpu.rfc5424 import DEFAULT_MAX_SD

    # the probe uploads no timestamp text; the assemble the rendered
    # [N, TS_W] block (device_common.fetch_encode_driver)
    ts_w = TS_W if assemble else 0
    _compiles(
        fused_routes._fused_rfc5424_gelf,
        spec(jnp.uint8, ROWS, MAX_LEN), spec(jnp.int32, ROWS),
        spec(jnp.uint8, ROWS, ts_w), spec(jnp.int32, ROWS),
        max_sd=DEFAULT_MAX_SD, suffix=b"\0",
        impl=aot._scan_impl_for("tpu"), assemble=assemble, extras=(),
        demand=fused_routes.DEMAND["rfc5424_gelf"])


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
