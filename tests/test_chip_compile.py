"""Compile-only gate: the device path at fleet scale, for a TPU v5e.

Every kernel and jitted tick of the assessment path is lowered and
compiled for one chip of a described ``v5e:2x2`` topology (nothing runs)
at the shapes of a 10,000-worker × 8-container cluster: 40,000 maps
padded to 65,536 attempt rows, 10,000 nodes, 4 job slots. The TPU
compiler refuses here what interpret mode on the CPU cannot see —
float64 kernel operands, scalar stores to vector memory, unaligned
blocks, more vector memory than a kernel may use. The platform regime
of a TPU is steered from the test: float32/int32 operands and
``interpret=False``.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.accel.base import TMARK, TPROG
from repro.core.arrays import ArraySnapshot, DeviceColumns

CAP = 65_536          # attempt rows, padded (40k–43k live)
N_NODES = 10_000
# (rows, nodes) per cluster: the fleet above, and the paper's testbed
# (20 workers; a 10 GB terasort stays under 256 attempt rows).
SIZES = {"fleet": (CAP, N_NODES), "testbed": (256, 20)}
JCAP = 4              # active-job slots
JOBS_CAP = 4          # job-registry slots
K_NEIGHBOR = 4        # GlanceConfig.size_neighbor
N_SCENARIOS = 32      # the sweep's scenario axis
N_FLOWS = 65_536      # bulk pricing: in-flight transfers
N_LINKS = 20_480


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A described chip cannot read back what it writes to the
    # persistent cache: keep it off while these compile.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cols(sharding, batch=(), cap=CAP, n_nodes=N_NODES):
    """ShapeDtypeStructs of the uploaded mirror in the TPU regime."""
    ref = ArraySnapshot(["n0"])

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(batch + shape, dtype, sharding=sharding)

    def dev_dtype(np_dtype):
        kind = np.dtype(np_dtype).kind
        return jnp.float32 if kind == "f" else (
            jnp.bool_ if kind == "b" else jnp.int32)

    cols = {name: s((cap,), dev_dtype(getattr(ref, name).dtype))
            for name in DeviceColumns._FILLS}
    cols.update({
        "order": s((cap,), jnp.int32), "tseg": s((cap,), jnp.int32),
        TMARK: s((cap,), jnp.int32), TPROG: s((cap,), jnp.float32),
        "job_local": s((JOBS_CAP,), jnp.int32),
        "n_rows": s((), jnp.int32), "n_jobs": s((), jnp.int32),
        "node_hb": s((n_nodes,), jnp.float32),
        "node_speed": s((n_nodes,), jnp.float32),
        "node_marked": s((n_nodes,), jnp.bool_),
        "one": s((), jnp.float32), "sf": s((), jnp.float32),
    })
    return cols


def _f(sharding, shape=()):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _i(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _b(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=sharding)


def _compile(fn, *args, **static):
    with jax.enable_x64(False):
        return jax.jit(fn, static_argnames=tuple(static)).lower(
            *args, **static).compile()


def _pallas_case(name, sh, size):
    from repro.accel import pallas_backend as P
    cap, n_nodes = SIZES[size]
    cols = _cols(sh, cap=cap, n_nodes=n_nodes)
    nh = _i(sh, (n_nodes, K_NEIGHBOR))
    jb = (JCAP,)
    return {
        "glance": (P.pallas_spatial, (cols, nh, _f(sh)),
                   {"jcap": JCAP, "interpret": False}),
        "temporal": (P.pallas_temporal,
                     (cols, _f(sh), _b(sh, jb), _b(sh, jb), _i(sh, jb)),
                     {"n_nodes": n_nodes, "interpret": False}),
        "late": (P.pallas_late, (cols, _f(sh), _f(sh), _f(sh)),
                 {"jcap": JCAP, "interpret": False}),
        "collective": (P.pallas_winning, (cols, _f(sh), _f(sh)),
                       {"jcap": JCAP, "interpret": False}),
        "reap": (P.pallas_reap, (cols, _f(sh)), {"interpret": False}),
    }[name]


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", ["glance", "temporal", "late",
                                    "collective", "reap"])
def test_pallas_assessment_kernel_compiles(kernel, size, one_chip):
    fn, args, static = _pallas_case(kernel, one_chip, size)
    compiled = _compile(fn, *args, **static)
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_bulk_pricing_kernel_compiles(one_chip):
    # The ``pallas`` bulk backend prices in XLA (Mosaic has no gather).
    from repro.accel.bulk import PallasBulk
    sh = one_chip
    price = PallasBulk()._price_core()
    with jax.enable_x64(False):
        compiled = price.lower(_f(sh, (N_LINKS,)), _i(sh, (N_FLOWS, 4)),
                               _b(sh, (N_FLOWS, 4))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def _xla_case(name, sh):
    from repro.accel import jax_backend as J
    cols = _cols(sh)
    nh = _i(sh, (N_NODES, K_NEIGHBOR))
    jb = (JCAP,)
    nodes = (N_NODES,)
    return {
        "spatial": (J.spatial_core, (cols, nh, _f(sh)), {"jcap": JCAP}),
        "temporal": (J.temporal_core,
                     (cols, _f(sh), _b(sh, jb), _b(sh, jb), _i(sh, jb)),
                     {"n_nodes": N_NODES}),
        "failure": (J.failure_core,
                    (_f(sh), _f(sh, nodes), _b(sh, nodes), _b(sh, nodes),
                     _f(sh, nodes), _f(sh)), {}),
        "late": (J.late_core, (cols, _f(sh), _f(sh), _f(sh)),
                 {"jcap": JCAP}),
        "winning": (J.winning_core, (cols, _f(sh), _f(sh)), {"jcap": JCAP}),
        "reap": (J.reap_core, (cols, _f(sh)), {}),
    }[name]


@pytest.mark.parametrize("core", ["spatial", "temporal", "failure", "late",
                                  "winning", "reap"])
def test_device_tick_compiles(core, one_chip):
    fn, args, static = _xla_case(core, one_chip)
    compiled = _compile(fn, *args, **static)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30


def test_batched_sweep_step_compiles(one_chip):
    from repro.accel.sweep import _sweep_jit
    sh = one_chip
    nodes = (N_NODES,)
    cols = _cols(sh, batch=(N_SCENARIOS,))
    cols["one"] = _f(sh, (N_SCENARIOS,))
    cols["sf"] = _f(sh, (N_SCENARIOS,))
    with jax.enable_x64(False):
        compiled = _sweep_jit(JCAP).lower(
            cols, _i(sh, (N_NODES, K_NEIGHBOR)), _f(sh), _f(sh), _f(sh),
            _f(sh), _b(sh, nodes), _f(sh, nodes), _f(sh)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 30
