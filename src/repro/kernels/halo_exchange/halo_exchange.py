"""Message-free ring halo exchange via Pallas async remote DMA.

This is the paper's technique *as a TPU kernel* (DESIGN.md §2/§6): instead of
matched message pairs (ppermute -> collective-permute), every device WRITES
its boundary strip directly into its neighbours' receive windows over ICI —
the TPU analogue of producing into a CXL.mem pooled buffer — and the only
synchronization is the DMA semaphore handshake:

    send semaphore  = the producer's "ready-to-read" signal   (Eq. 2, 1st)
    recv semaphore  = the consumer's completion wait           (Eq. 2, 2nd)

i.e. exactly the 2 × CXL_ATOMIC_LAT cost the transfer model prices for
message-free communication, with zero per-message matching or copies on the
critical path.

The kernel runs under ``shard_map`` (one program per device along the ring
axis).  A barrier semaphore first guarantees the neighbour's window is
reusable (receiver "ready-to-write"), then both directional remote copies
proceed concurrently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat import axis_size


def _halo_kernel(strip_lo_ref, strip_hi_ref, recv_lo_ref, recv_hi_ref,
                 send_sem, recv_sem, *, axis: str):
    """Push ``strip_lo`` to the left neighbour's ``recv_hi`` window and
    ``strip_hi`` to the right neighbour's ``recv_lo`` window."""
    my_id = jax.lax.axis_index(axis)
    n = axis_size(axis)
    left = jax.lax.rem(my_id - 1 + n, n)
    right = jax.lax.rem(my_id + 1, n)

    # receiver ready-to-write: all devices on the ring reach this point
    # before any window is overwritten (the 2nd atomic of paper Eq. 2).
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, 1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(barrier, 1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)

    copy_lo = pltpu.make_async_remote_copy(
        src_ref=strip_lo_ref, dst_ref=recv_hi_ref,
        send_sem=send_sem.at[0], recv_sem=recv_sem.at[0],
        device_id=left, device_id_type=pltpu.DeviceIdType.LOGICAL)
    copy_hi = pltpu.make_async_remote_copy(
        src_ref=strip_hi_ref, dst_ref=recv_lo_ref,
        send_sem=send_sem.at[1], recv_sem=recv_sem.at[1],
        device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)
    copy_lo.start()
    copy_hi.start()
    copy_lo.wait()   # producer ready-to-read signal observed (Eq. 2, 1st)
    copy_hi.wait()


@functools.partial(jax.jit, static_argnames=("axis", "collective_id"))
def _ring_exchange_device(strip_lo, strip_hi, axis: str,
                          collective_id: int = 7):
    """Per-device body: (strip_lo, strip_hi) -> (from_left, from_right)."""
    out_shape = [jax.ShapeDtypeStruct(strip_lo.shape, strip_lo.dtype),
                 jax.ShapeDtypeStruct(strip_hi.shape, strip_hi.dtype)]
    return pl.pallas_call(
        functools.partial(_halo_kernel, axis=axis),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=out_shape,
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
    )(strip_lo, strip_hi)


def ring_halo_exchange(strip_lo, strip_hi, axis: str, mesh=None):
    """Message-free ring exchange along ``axis`` (call inside shard_map).

    Each rank publishes its low/high boundary strips; returns
    (from_prev, from_next) — the neighbours' strips, delivered by remote
    DMA into this rank's windows.  TPU only; CPU paths use
    ``repro.comm.message_free`` (the shared-window emulation).
    """
    return _ring_exchange_device(strip_lo, strip_hi, axis)
