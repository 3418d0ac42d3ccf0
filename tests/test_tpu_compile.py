"""Compile the main path's kernels for a described TPU v5e (2x2) at real
size: the TPU compiler refuses here what interpret mode cannot see
(unaligned tiles, scoped-VMEM overruns, a kernel that does not fit).
Nothing runs; a pass says the chip's compiler accepts the program.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

THERMAL = (32, 32, 32, 8)
C64 = jnp.complex64


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _text(lowered) -> str:
    return lowered.compile().as_text()


@pytest.mark.parametrize("src_parity", [0, 1])
def test_eo_dslash_compiles_at_thermal(one_chip, src_parity):
    from repro.kernels.dslash.ops import DEFAULT_T_BLOCK, _dslash_half_call
    X, Y, Z, T = THERMAL
    U = jax.ShapeDtypeStruct((4, X // 2, Y, Z, T, 3, 3), C64,
                             sharding=one_chip)
    psi = jax.ShapeDtypeStruct((X // 2, Y, Z, T, 4, 3), C64,
                               sharding=one_chip)
    text = _text(_dslash_half_call.lower(U, U, psi, src_parity,
                                         t_block=DEFAULT_T_BLOCK,
                                         interpret=False))
    assert "tpu_custom_call" in text


def test_full_dslash_compiles_at_thermal(one_chip):
    from repro.kernels.dslash.ops import DEFAULT_T_BLOCK, _dslash_call
    X, Y, Z, T = THERMAL
    U = jax.ShapeDtypeStruct((4, X, Y, Z, T, 3, 3), C64, sharding=one_chip)
    psi = jax.ShapeDtypeStruct((X, Y, Z, T, 4, 3), C64, sharding=one_chip)
    text = _text(_dslash_call.lower(U, psi, t_block=DEFAULT_T_BLOCK,
                                    interpret=False))
    assert "tpu_custom_call" in text


def test_sharded_pallas_hop_compiles_over_four(topo, no_persistent_cache):
    """The per-shard hop of the thermal lattice over four chips: halo
    ppermutes around the kernel on the padded local volume 32^3 x (2+2)."""
    from repro.distributed.sharding import lattice_eo_specs
    from repro.kernels.dslash.ops import sharded_t_block
    from repro.lqcd.multichip_eo import _half_hop_pallas_local
    X, Y, Z, T = THERMAL
    n = 4
    t_pad = T // n + 2
    mesh = Mesh(np.array(topo.devices[:n]), ("model",))
    u_spec, p_spec = lattice_eo_specs("model")
    hop = partial(_half_hop_pallas_local, src_parity_eff=1,
                  t_block=sharded_t_block((X // 2, Y, Z, t_pad)),
                  interpret=False, axis_name="model", n_shards=n)
    fn = jax.jit(jax.shard_map(hop, mesh=mesh,
                               in_specs=(u_spec, u_spec, p_spec),
                               out_specs=p_spec, check_vma=False))
    U = jax.ShapeDtypeStruct((4, X // 2, Y, Z, n * t_pad, 3, 3), C64,
                             sharding=NamedSharding(mesh, u_spec))
    psi = jax.ShapeDtypeStruct((X // 2, Y, Z, T, 4, 3), C64,
                               sharding=NamedSharding(mesh, p_spec))
    text = _text(fn.lower(U, U, psi))
    assert "tpu_custom_call" in text and "collective-permute" in text


def test_blocked_lu_compiles_at_8192(one_chip):
    from repro.hpl.lu import blocked_lu
    a = jax.ShapeDtypeStruct((8192, 8192), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda m: blocked_lu(m, 256)).lower(a).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_planes_inner_cg_compiles_at_thermal(one_chip):
    """The single-device inner CG on bf16 planes, the thermal cell's hot
    loop: its temporaries fit in a fraction of what the complex layout's
    padded fields needed (1.73 GB for this program)."""
    from repro.lqcd import cg
    X, Y, Z, T = THERMAL
    links = jax.ShapeDtypeStruct((2, 4, 3, 3, T, Z, Y * X // 2),
                                 jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((X // 2, Y, Z, T, 4, 3), C64,
                               sharding=one_chip)

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=one_chip)

    compiled = cg._eo_inner.lower(
        links, links, rhs, scalar(jnp.float32), scalar(jnp.float32),
        scalar(jnp.int32), inner_dtype=jnp.bfloat16).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
