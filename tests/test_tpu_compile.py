"""The Pallas kernels of the main paths, compiled for a TPU v5e at real
widths — no chip needed.

The TPU compiler is installed with JAX; ``topologies.get_topology_desc``
describes a ``v5e:2x2`` host without one attached, and lowering against
its devices runs the real Mosaic compile, which refuses what interpret
mode accepts (64-bit types, unaligned tiles, too much VMEM, a kernel
that cannot be partitioned).  Each test asserts the kernel survived as a
``tpu_custom_call``.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, so a
worker that collects this file without running it must not touch it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

S_SCEN, N_SAMPLES, N_SEG = 4096, 65536, 64           # pricing kernel
QWEN_HEADS, QWEN_KV, QWEN_HD, SEQ = 16, 2, 128, 2048  # qwen2.5-3b attention
MAMBA_D_INNER, MAMBA_STATE, MAMBA_L = 8192, 16, 1024  # falcon-mamba-7b


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip lands in the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fused_bracket_segsum_compiles_f32(one_chip):
    from repro.kernels.sweep_bracket import fused_bracket_segsum
    group = (_spec((N_SAMPLES,), "float32", one_chip),
             _spec((N_SAMPLES,), "float32", one_chip),
             _spec((N_SAMPLES,), "int32", one_chip))
    scen = _spec((S_SCEN,), "float32", one_chip)
    _assert_kernel(functools.partial(fused_bracket_segsum, n_seg=N_SEG,
                                     interpret=False),
                   group, group, group, scen, scen)


def test_segment_sum_pallas_compiles_f32(one_chip):
    from repro.kernels.sweep_bracket import segment_sum_pallas
    _assert_kernel(functools.partial(segment_sum_pallas, n_seg=N_SEG,
                                     interpret=False),
                   _spec((S_SCEN, N_SAMPLES), "float32", one_chip),
                   _spec((N_SAMPLES,), "int32", one_chip))


def test_flash_attention_compiles_at_qwen_heads(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = _spec((1, SEQ, QWEN_HEADS, QWEN_HD), "bfloat16", one_chip)
    kv = _spec((1, SEQ, QWEN_KV, QWEN_HD), "bfloat16", one_chip)
    _assert_kernel(functools.partial(flash_attention, causal=True,
                                     interpret=False), q, kv, kv)


@pytest.mark.parametrize("n_kv,group,block,max_blocks,layers", [
    (QWEN_KV, QWEN_HEADS // QWEN_KV, 64, 40, 36),      # qwen2.5-3b serving
    (1, 20, 256, 34, 2)], ids=["qwen", "jamba"])       # jamba2-3b serving
def test_paged_attention_compiles_at_serving_widths(one_chip, n_kv, group,
                                                    block, max_blocks,
                                                    layers):
    from repro.kernels.paged_attention import paged_attention
    slots = 32
    pool = _spec((layers, slots * max_blocks + 1, n_kv, block, QWEN_HD),
                 "bfloat16", one_chip)
    _assert_kernel(functools.partial(paged_attention, interpret=False),
                   _spec((slots, n_kv, group, QWEN_HD), "bfloat16",
                         one_chip), pool, pool,
                   _spec((slots, max_blocks), "int32", one_chip),
                   _spec((slots,), "int32", one_chip),
                   _spec((), "int32", one_chip))


def test_mamba_scan_compiles_at_falcon_mamba_widths(one_chip):
    from repro.kernels.mamba_scan import mamba_scan
    x = _spec((1, MAMBA_L, MAMBA_D_INNER), "float32", one_chip)
    bc = _spec((1, MAMBA_L, MAMBA_STATE), "float32", one_chip)
    _assert_kernel(functools.partial(mamba_scan, interpret=False), x, x, bc,
                   bc, _spec((MAMBA_D_INNER, MAMBA_STATE), "float32",
                             one_chip),
                   _spec((MAMBA_D_INNER,), "float32", one_chip))


def test_halo_exchange_ring_compiles_on_four_devices(topo):
    from repro.compat import make_mesh, shard_map
    from repro.kernels.halo_exchange.halo_exchange import ring_halo_exchange
    mesh = make_mesh((4,), ("ring",), devices=topo.devices)

    def body(block):
        prev, nxt = ring_halo_exchange(block[:1], block[-1:], "ring")
        return jnp.concatenate([prev, nxt], axis=0)

    ring = shard_map(body, mesh=mesh, in_specs=P("ring"),
                     out_specs=P("ring"), check_vma=False)
    _assert_kernel(ring, _spec((4 * 64, 1024), "float32",
                               NamedSharding(mesh, P("ring"))))
