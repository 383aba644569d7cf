"""Compiles of the main path for a described TPU v5e 2x2 (no chip needed).

The TPU compiler is installed with JAX, and it compiles for a topology
that is described rather than attached. These compiles catch what the
interpret-mode Pallas tests cannot: tiles the Mosaic compiler refuses,
scoped VMEM over budget, programs that do not fit HBM, collectives that
cannot be partitioned. Nothing runs, so they say nothing about results
or times.

The topology is described inside a module fixture only: libtpu can be
loaded by one process at a time, and a description at import would make
xdist workers collect different tests. Pallas is steered off interpret
mode with ``monkeypatch``; the persistent compile cache is off around
the compiles (a described-chip entry cannot be read back here).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

import distributedfft_tpu as dfft
from distributedfft_tpu.analysis import hloscan
from distributedfft_tpu.ops import fft as lf
from distributedfft_tpu.ops import pallas_fft

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_for_tpu(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(pallas_fft, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _check(compiled, marker):
    txt = compiled.as_text()
    assert marker in txt, f"no {marker} in the compiled program"
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB per device"


@pytest.mark.parametrize("n", [256, 512])
def test_pallas_3d_roundtrip_compiles(one_chip, n):
    x = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda v: pallas_fft.irfftn_3d(
        pallas_fft.rfftn_3d(v), (n, n, n))).lower(x).compile()
    _check(compiled, "tpu_custom_call")


def test_fused_wire_decode_fft_compiles_at_1024(one_chip):
    """The fused wire decode + DFT kernel at its widest block (1024 =
    ``_N_MAX``: both 1024x1024 f32 DFT planes resident in VMEM)."""
    y = jax.ShapeDtypeStruct((2, 4096, 1024), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(lambda v: pallas_fft.decode_fft_fused(
        v, jnp.complex64, axis=1)).lower(y).compile()
    _check(compiled, "tpu_custom_call")


def _slab4_1024(topo, direction, config):
    """The slab 1024^3 program of one direction on the described 2x2:
    the plan (its config resolved on the mesh's platform) and the
    compiled program, traced with 64-bit types off as on the chip (the
    xla C2R's twiddle is computed in complex128 under x64, which the TPU
    compiler refuses)."""
    mesh = Mesh(np.array(topo.devices), ("p",))
    with jax.enable_x64(False):
        plan = dfft.SlabFFTPlan(dfft.GlobalSize(1024, 1024, 1024),
                                dfft.SlabPartition(4), config, mesh=mesh)
        aval = hloscan._input_aval(plan, direction)
        sharding = (plan.input_sharding if direction == "forward"
                    else plan.output_sharding)
        x = jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sharding)
        return plan, hloscan._builder(plan, direction).lower(x).compile()


def _bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def _default_within_xla(topo, direction):
    """The default Config on a (described) TPU resolves to the MXU
    matmuls at HIGHEST, compiles with the all-to-all, and holds no more
    argument + output + temporary bytes per device than the explicit xla
    program of the same direction."""
    plan, compiled = _slab4_1024(topo, direction, dfft.Config())
    assert plan.config.fft_backend == "matmul"
    assert plan.config.mxu_precision == "highest"
    _check(compiled, "all-to-all")
    _, xla = _slab4_1024(topo, direction, dfft.Config(fft_backend="xla"))
    assert _bytes(compiled) <= _bytes(xla), (
        f"{direction}: default {_bytes(compiled) / 1e9:.4f} GB > "
        f"xla {_bytes(xla) / 1e9:.4f} GB per device")


def test_slab4_1024_forward_compiles(topo):
    _default_within_xla(topo, "forward")


def test_slab4_1024_inverse_compiles(topo):
    _default_within_xla(topo, "inverse")


def _assert_whole_tile_writes(compiled, local_batch):
    """Every write of the chunk loop's answers into a stack fills whole
    (8,128) tiles: where a dynamic-update-slice's layout puts a dimension
    in the sublanes (second minor-most), the update covers that dimension
    whole or in multiples of 8. One image written into a stack whose
    batch sits in the sublanes does not. The result keeps the default
    layout, which every consumer (and a cached one) expects."""
    txt = compiled.as_text()
    dims_of = {name: [int(d) for d in dims.split(",") if d]
               for name, dims in re.findall(r"%(\S+) = \w+\[([\d,]*)\]",
                                            txt)}
    writes = re.findall(r"= f32\[([\d,]+)\]\{([\d,]+):T\([^=]*"
                        r"dynamic-update-slice\(%[^,]+, %([^,]+),", txt)
    stacks = 0
    for dims, m2m, update in writes:
        dims = [int(d) for d in dims.split(",")]
        sub = [int(d) for d in m2m.split(",")][1]
        n = dims_of[update][sub]
        assert n == dims[sub] or n % 8 == 0, (
            f"f32{dims}{{{m2m}}} written {n} of {dims[sub]} sublanes")
        stacks += np.prod(dims[:-2]) == local_batch
    assert stacks, "no dynamic-update-slice of the stack"
    default = compiled.output_formats.layout.major_to_minor
    assert default == (2, 0, 1), default


def test_batched2d_chunk_loop_writes_whole_tiles(one_chip, monkeypatch):
    """The one-chip chunked forward as ``_build`` makes it (the batched
    benchmark cell's program at 16 x 1024^2): the plan reads its platform
    as on the chip."""
    with jax.enable_x64(False):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            plan = dfft.Batched2DFFTPlan(16, 1024, 1024,
                                         dfft.SlabPartition(1),
                                         dfft.Config(), batch_chunk=1)
        x = jax.ShapeDtypeStruct((16, 1024, 1024), jnp.float32,
                                 sharding=one_chip)
        compiled = plan._build(forward=True).lower(x).compile()
    _assert_whole_tile_writes(compiled, 16)


def test_batched2d_sharded_chunk_loop_writes_whole_tiles(topo):
    """``shard="batch"`` over the described 2x2 (the ``shard_map`` path
    that ``serve/`` runs): 16 images a chip."""
    mesh = Mesh(np.array(topo.devices), ("p",))
    with jax.enable_x64(False):
        plan = dfft.Batched2DFFTPlan(64, 1024, 1024, dfft.SlabPartition(4),
                                     dfft.Config(), mesh=mesh,
                                     batch_chunk=1)
        x = jax.ShapeDtypeStruct((64, 1024, 1024), jnp.float32,
                                 sharding=plan.input_sharding)
        compiled = plan._build(forward=True).lower(x).compile()
    _assert_whole_tile_writes(compiled, 16)


def test_batched2d_xla_pairs_rows_on_a_described_v5e(topo):
    """On the described 2x2 (its devices' platform is "tpu") the float32
    xla plan pairs the rows of its R2C (``plan.r2c_paired``), and the
    compiled forward keeps whole-tile writes and reads the FFT's output
    through no reverse op (``ops/fft._paired_rfft``)."""
    mesh = Mesh(np.array(topo.devices), ("p",))
    with jax.enable_x64(False):
        plan = dfft.Batched2DFFTPlan(64, 2048, 2048, dfft.SlabPartition(4),
                                     dfft.Config(fft_backend="xla"),
                                     mesh=mesh, batch_chunk=1)
        assert plan.r2c_paired
        x = jax.ShapeDtypeStruct((64, 2048, 2048), jnp.float32,
                                 sharding=plan.input_sharding)
        compiled = plan._build(forward=True).lower(x).compile()
    assert " reverse(" not in compiled.as_text()
    _assert_whole_tile_writes(compiled, 16)


def _batched_cell_c2r(one_chip, monkeypatch, paired):
    """The batched benchmark cell's C2R (48 x 4096^2, chunk 1, float32,
    xla) compiled for one described v5e chip, its rows paired or not
    (``ops/fft.PAIRED_C2R_MIN_EXTENT`` out of reach)."""
    if not paired:
        monkeypatch.setattr(lf, "PAIRED_C2R_MIN_EXTENT", 1 << 30)
    with jax.enable_x64(False):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            plan = dfft.Batched2DFFTPlan(48, 4096, 4096,
                                         dfft.SlabPartition(1),
                                         dfft.Config(fft_backend="xla"),
                                         batch_chunk=1)
        assert plan.c2r_paired is paired
        c = jax.ShapeDtypeStruct((48, 4096, 2049), jnp.complex64,
                                 sharding=one_chip)
        return plan._build(forward=False).lower(c).compile()


def test_batched2d_cell_c2r_pairs_rows_on_a_described_v5e(one_chip,
                                                           monkeypatch):
    """The cell's paired C2R reads its spectrum through no reverse op
    (``ops/fft._paired_irfft``), and reserves no more temporaries than
    the unpaired program: the chip is nearly full while it runs."""
    compiled = _batched_cell_c2r(one_chip, monkeypatch, True)
    assert " reverse(" not in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    unpaired = _batched_cell_c2r(one_chip, monkeypatch, False)
    assert temp <= unpaired.memory_analysis().temp_size_in_bytes + 1e6, (
        f"{temp / 1e9:.4f} GB against "
        f"{unpaired.memory_analysis().temp_size_in_bytes / 1e9:.4f}")


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_one_chip_1024_default_chunks_and_fits(topo, direction):
    """The default Config's one-chip 1024^3 plan on a described v5e (a
    mesh of one of its devices, so the plan reads the TPU's platform and
    memory) chooses its chunk count by itself, and each direction holds
    at most 14.5 GB of argument + output + temporaries: the fused
    program needs 17.3, more than the chip's 15.75 GiB."""
    mesh = Mesh(np.array(topo.devices[:1]), ("p",))
    with jax.enable_x64(False):
        plan = dfft.SlabFFTPlan(dfft.GlobalSize(1024, 1024, 1024),
                                dfft.SlabPartition(1), dfft.Config(),
                                mesh=mesh)
        assert plan.fft3d and plan.config.fft_backend == "matmul"
        assert plan.fft3d_chunk_from == "memory" and plan.fft3d_chunk
        aval = hloscan._input_aval(plan, direction)
        x = jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
        compiled = hloscan._builder(plan, direction).lower(x).compile()
    assert _bytes(compiled) <= 14.5e9, f"{_bytes(compiled) / 1e9:.2f} GB"
