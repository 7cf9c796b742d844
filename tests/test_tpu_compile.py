"""Compile the main path and the Pallas kernels for a TPU v5e without one.

The TPU compiler is installed here, so every program below is compiled
for a described (not attached) ``v5e:2x2`` topology: the collective
write and read programs of ``chip_smoke.py`` at its per-round shapes
(fewer rounds), on one chip and on the (2,1,2) mesh, must compile and
fit a chip's 16 GiB. Nothing runs. The six Pallas kernels do not compile
for v5e yet; each is a strict xfail naming what the compiler refuses, to
be flipped as the kernels are fixed.

The topology is described inside a fixture (never at import), so under
several test workers only the worker given this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core.tam import make_tam_write
from repro.core.twophase import make_twophase_read, make_twophase_write
from repro.kernels import coalesce_kernel, fused_round, pack, sort
from repro.launch.mesh import make_io_mesh

V5E_HBM_BYTES = 16 * 2**30
ROUNDS = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip can be written to the persistent
    cache but not read back; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _phase1_args(mesh, program):
    n_ranks, n_nodes = mesh.size, mesh.shape["node"]
    reqs, req_elems, cb, layout = chip_smoke.phase1_sizes(
        n_ranks, n_nodes, max_rounds=ROUNDS)
    rank = NamedSharding(mesh, P(chip_smoke.RANK_AXES))

    def spec(*shape, sharding=rank):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    args = (spec(n_ranks, reqs), spec(n_ranks, reqs), spec(n_ranks),
            spec(n_ranks, reqs * req_elems))
    if program == "read":
        args = args[:3] + (spec(n_nodes, layout.file_len // n_nodes,
                                sharding=NamedSharding(mesh, P("node"))),)
    return chip_smoke.io_config(reqs, req_elems, cb, 2), layout, args


@pytest.mark.parametrize("chips", sorted(chip_smoke.MESHES))
@pytest.mark.parametrize("program", ["twophase", "tam", "read"])
def test_main_path_compiles_for_v5e(topo, no_compile_cache, chips, program):
    mesh = make_io_mesh(*chip_smoke.MESHES[chips],
                        devices=topo.devices[:chips])
    cfg, layout, args = _phase1_args(mesh, program)
    make = {"twophase": make_twophase_write, "tam": make_tam_write,
            "read": make_twophase_read}[program]
    compiled = jax.jit(make(mesh, layout, cfg)).lower(*args).compile()
    m = compiled.memory_analysis()
    per_device = (m.argument_size_in_bytes + m.output_size_in_bytes
                  + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < per_device < V5E_HBM_BYTES
    if chips > 1:
        assert "all-to-all" in compiled.as_text() or program == "read"


REQ_BLOCK = 1024
TILE_LEN = 2 * pack.TILE


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


KERNELS = {
    "bitonic_sort": (
        NotImplementedError,
        "`rev` is not lowered: the compare-exchange partner view "
        "x.reshape(-1, 2, j)[:, ::-1, :] (kernels/sort.py)",
        lambda s: sort.bitonic_sort.lower(
            _i32((1, REQ_BLOCK), s), _i32((1, REQ_BLOCK), s),
            _i32((1, REQ_BLOCK), s), interpret=False)),
    "pack": (
        NotImplementedError,
        "only 2D gather is supported: sorted_keys[mid_c] and data[src] "
        "(kernels/pack.py)",
        lambda s: pack.pack.lower(
            _i32((REQ_BLOCK,), s), _i32((REQ_BLOCK,), s),
            _i32((REQ_BLOCK,), s), _i32((TILE_LEN,), s), _i32((), s),
            out_len=TILE_LEN, interpret=False)),
    "coalesce": (
        NotImplementedError,
        "`scatter` is not lowered: the .at[].set run compaction "
        "(kernels/coalesce_kernel.py)",
        lambda s: coalesce_kernel.coalesce.lower(
            _i32((1, REQ_BLOCK), s), _i32((1, REQ_BLOCK), s),
            interpret=False)),
    "fused_sort_pack": (
        NotImplementedError,
        "`rev` is not lowered (the bitonic body), behind it the whole "
        "payload as one block and the gather dd[src] "
        "(kernels/fused_round.py)",
        lambda s: fused_round.fused_sort_pack.lower(
            _i32((REQ_BLOCK,), s), _i32((REQ_BLOCK,), s),
            _i32((REQ_BLOCK,), s), _i32((TILE_LEN,), s), _i32((), s),
            out_len=TILE_LEN, interpret=False)),
    "zero_skip_encode": (
        ValueError,
        "a (1, n) block breaks the 8x128 tiling rule, behind it a "
        ".at[idx].set scatter (kernels/fused_round.py)",
        lambda s: fused_round.zero_skip_encode.lower(
            _i32((8, REQ_BLOCK), s), interpret=False)),
    "zero_skip_decode": (
        ValueError,
        "a (1, n) block breaks the 8x128 tiling rule, behind it a "
        ".at[idx].set scatter (kernels/fused_round.py)",
        lambda s: fused_round.zero_skip_decode.lower(
            _i32((8, REQ_BLOCK), s), _i32((8, REQ_BLOCK), s),
            interpret=False)),
}


@pytest.mark.parametrize("kernel", [
    pytest.param(name, marks=pytest.mark.xfail(strict=True, raises=raises,
                                               reason=reason))
    for name, (raises, reason, _) in KERNELS.items()])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel):
    KERNELS[kernel][2](one_chip).compile()
