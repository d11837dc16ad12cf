"""Cross-device hit-row gather for the mesh-sharded fused index.

The pod-local dispatch tier (``parallel/mesh.py MeshFusedIndex``) answers
each query on exactly ONE device — the owner of the query's dataset
shard — and every other device contributes zeros. Combining those
per-device partials into a replicated result is a gather in sum
clothing: the owner's block plus (n-1) zero blocks. This module provides
that combine in two implementations behind one call:

- **TPU**: a Pallas ring pass built on ``pltpu.make_async_remote_copy``
  (the right-permute remote-DMA idiom): each step every device DMAs its
  current block to its right neighbour over ICI and accumulates what it
  received, so after n-1 steps every device holds the full sum without
  ever staging the [B, R] row block through XLA's all-reduce scratch.
- **portable** (CPU/GPU/tests): ``lax.all_gather`` + a sum over the
  gathered device axis — semantically identical, runs anywhere
  shard_map does (the forced-host-device CI mesh included).

Both run INSIDE a shard_map body; the caller picks the implementation
at trace time (:func:`default_impl`), never inside the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def default_impl() -> str:
    """The combine a mesh program traces with on this backend: the
    Pallas ring on TPU, the portable ``all_gather`` everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "portable"


def gather_partials_portable(x, axis: str):
    """Sum per-device partial blocks into a replicated block.

    ``x``: the device-local partial (owner carries real values, everyone
    else zeros). Uses ``all_gather`` + sum rather than ``psum`` so the
    gathered-axis layout mirrors the TPU ring pass (and the replication
    checker's view of both paths matches: neither is inferable, the
    caller runs under ``check_vma=False``)."""
    g = jax.lax.all_gather(x, axis)  # [n_dev, ...]
    return jnp.sum(g, axis=0)


def _ring_step_kernel(x_ref, out_ref, send_sem, recv_sem, *, axis: str):
    """One ring rotation: DMA my block to my right neighbour's output
    buffer and wait for the left neighbour's block to land in mine."""
    from jax.experimental.pallas import tpu as pltpu

    me = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me + n - 1, n)
    mesh_id = pltpu.DeviceIdType.MESH
    # both neighbours must be inside THIS step before anything lands in
    # their output buffer: a device still in the previous step may have
    # that memory live under another name
    barrier = pltpu.get_barrier_semaphore()
    for nb in (left, right):
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=(nb,), device_id_type=mesh_id
        )
    pltpu.semaphore_wait(barrier, 2)
    copy = pltpu.make_async_remote_copy(
        src_ref=x_ref,
        dst_ref=out_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=(right,),
        device_id_type=mesh_id,
    )
    copy.start()
    copy.wait()


@functools.lru_cache(maxsize=None)
def _ring_step_fn(axis: str, shape: tuple, dtype_name: str):
    import jax.numpy as _jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = _jnp.dtype(dtype_name)
    return pl.pallas_call(
        functools.partial(_ring_step_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        # the barrier semaphore is shared by the devices of one
        # collective; every step of every ring pass uses the same one,
        # in program order
        compiler_params=pltpu.CompilerParams(collective_id=0),
    )


def gather_partials_tpu(x, axis: str, n_dev: int):
    """TPU ring combine of per-device partials via async remote DMA.

    After step k every device holds the block that started k positions
    to its left; accumulating each arrival reconstructs the full sum on
    every device in n-1 ICI hops — the Pallas analogue of the portable
    all_gather+sum, with the DMA schedule explicit."""
    if n_dev <= 1:
        return x
    step = _ring_step_fn(axis, tuple(x.shape), str(x.dtype))
    acc = x
    blk = x
    for _ in range(n_dev - 1):
        blk = step(blk)
        acc = acc + blk
    return acc


def gather_partials(x, axis: str, n_dev: int, *, impl: str = "portable"):
    """Dispatch on the implementation chosen at trace time.

    ``impl``: ``"pallas"`` (TPU remote-DMA ring) or ``"portable"``
    (all_gather+sum). The caller decides OUTSIDE the shard_map body
    (:func:`default_impl`) — backend probing does not trace."""
    if impl == "pallas":
        return gather_partials_tpu(x, axis, n_dev)
    return gather_partials_portable(x, axis)


def gather_partials_many(xs, axis: str, n_dev: int, *, impl: str = "portable"):
    """ONE combined gather pass over several partial blocks.

    The mesh plane program produces four per-query blocks to reassemble
    (hit rows, masked call/token popcounts, the sample-hit OR words) —
    all int32, all sharing the leading batch axis. Ring-combining them
    separately costs 4x(n-1) ICI hops and 4 semaphore pairs per step;
    concatenating along the trailing axis first makes it ONE ring pass
    (n-1 hops) over a single contiguous block, then a free split. The
    portable path concatenates too, so both implementations see the
    identical block layout."""
    xs = tuple(xs)
    if len(xs) == 1:
        return (gather_partials(xs[0], axis, n_dev, impl=impl),)
    # split points are static shape arithmetic (python ints, never
    # tracers — jnp.split needs concrete indices inside the trace)
    splits, acc = [], 0
    for x in xs[:-1]:
        acc += int(x.shape[-1])
        splits.append(acc)
    cat = jnp.concatenate(xs, axis=-1)
    out = gather_partials(cat, axis, n_dev, impl=impl)
    return tuple(jnp.split(out, splits, axis=-1))
