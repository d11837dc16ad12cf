"""Dataset-sharded query execution over a ``jax.sharding.Mesh``.

This is the TPU-native replacement for the reference's *entire* distributed
fan-out/fan-in apparatus: the 500-thread dataset scatter (reference:
shared_resources/variantutils/search_variants.py:77-118), the SNS splitQuery/
performQuery process boundaries, and the DynamoDB atomic fan-in counter
(dynamodb/variant_queries.py:45-59) collapse into ONE compiled program:

- datasets (one index shard per (dataset, vcf)) are stacked on a leading
  axis and sharded over mesh axis ``d`` — the scatter is the sharding;
- every device answers the full query batch against its local dataset
  shards (vmap over datasets × vmap over queries);
- fan-in is ``lax.psum`` over ``d`` for the cross-dataset aggregates
  (exists / call_count / allele counts), i.e. the ICI collective replaces
  the counter+poll state machine entirely;
- per-dataset results (the PerformQueryResponse set) stay device-sharded
  and are gathered only when record-granularity materialisation needs them.

Multi-host: the same program runs under jax.distributed with a global mesh;
shardings are expressed once and XLA lays collectives onto ICI/DCN.
"""

from __future__ import annotations

import contextlib
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.columnar import N_CHROM_CODES, VariantIndexShard
from ..ops.kernel import (
    LANES,
    DeviceIndex,
    _query_one,
    bisect_iters,
    encode_queries,
    pack_queries,
    pad_shard_columns,
    padded_rows,
    unpack_queries,
)

AXIS = "d"


def __getattr__(name: str):
    """Module back-compat property (PEP 562), served by the device
    flight recorder (telemetry.py): the old unlocked module-global
    increment raced across request threads on real accelerators (no
    ``_CPU_COLLECTIVE_LOCK`` there); the recorder's lock now owns it
    and the name stays readable for tests.

    ``N_LAUNCHES``: compiled mesh-program dispatches, one per
    :func:`sharded_query` launch; kernel.py N_LAUNCHES and
    scatter_kernel.N_DISPATCHES count the single-device families.
    """
    from ..telemetry import flight_recorder

    if name == "N_LAUNCHES":
        return flight_recorder.mesh_launches
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )


def make_mesh(
    n_devices: int | None = None,
    axis: str = AXIS,
    *,
    devices=None,
    backend: str | None = None,
) -> Mesh:
    """1-D device mesh.

    Device selection is explicit: pass ``devices`` (an ordered device
    list — multi-host callers hand in the global set) or ``backend``
    (``jax.local_devices(backend=...)``, so a host with both a TPU and
    a CPU backend pins the mesh to the intended one). The default stays
    ``jax.devices()`` — the process-global view ``init_multihost``
    federates. ``n_devices`` truncates to a prefix; an empty selection
    is an error here, not a zero-device Mesh that fails later inside
    some collective with an unrelated message."""
    if devices is None:
        devices = (
            jax.local_devices(backend=backend)
            if backend is not None
            else jax.devices()
        )
    devices = list(devices)
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    if not devices:
        raise ValueError(
            "make_mesh: 0 devices selected (check the devices=/backend= "
            "selection and jax platform initialisation)"
        )
    return Mesh(np.array(devices), (axis,))


class StackedIndex:
    """D dataset shards padded to a common row count and stacked: [D, Np].

    The stack is the unit the mesh shards: axis 0 is partitioned over the
    ``d`` mesh axis. D is padded up to a multiple of the mesh size with
    empty datasets (all-zero chrom_offsets -> no query ever selects a row).
    """

    def __init__(
        self,
        shards: list[VariantIndexShard],
        *,
        n_datasets_padded: int | None = None,
        pad_unit: int = DeviceIndex.PAD_UNIT,
    ):
        if not shards:
            raise ValueError("StackedIndex needs at least one shard")
        self.shards = shards
        d = len(shards)
        d_pad = n_datasets_padded or d
        if d_pad < d:
            raise ValueError("n_datasets_padded < number of shards")
        n_max = max(s.n_rows for s in shards)
        n_pad = padded_rows(n_max, pad_unit)
        self.n_datasets = d
        self.n_datasets_padded = d_pad
        self.n_padded = n_pad

        # all padding happens host-side; device transfer occurs exactly once,
        # in shard_to_mesh, with the real sharding
        per = [pad_shard_columns(s, n_pad) for s in shards]
        names = [k for k in per[0] if k != "chrom_offsets"]
        self.arrays = {}
        for name in names:
            mats = [p[name] for p in per]
            # padding datasets reuse shard 0's padded tail row, whose values
            # are the canonical fills; their all-zero chrom_offsets make them
            # unreachable regardless
            fill = mats[0][-1]
            self.arrays[name] = np.stack(
                mats + [np.full_like(mats[0], fill)] * (d_pad - d)
            )
        self.arrays["chrom_offsets"] = np.stack(
            [p["chrom_offsets"] for p in per]
            + [np.zeros(N_CHROM_CODES + 1, np.int32)] * (d_pad - d)
        )
        self.n_iters = bisect_iters(n_pad)

    def shard_to_mesh(self, mesh: Mesh, axis: str = AXIS) -> dict:
        """Device-put the stack with axis 0 partitioned over ``axis``."""
        sharding = NamedSharding(mesh, P(axis))
        # straight from the host, each device its slice: an array made
        # whole on the default device first would stand there beside
        # what that chip already owns
        return {
            k: jax.device_put(lane_rows(k, np.asarray(v)), sharding)
            for k, v in self.arrays.items()
        }


def lane_rows(name: str, stacked: np.ndarray) -> np.ndarray:
    """A stacked column ``[D, n, ...]`` as it is resident: ``[D, n / 128,
    128, ...]``, a view. ``_query_one`` reads a dataset's window as the
    128-lane rows it lies in; resident ``[D, n]`` the device tiles eight
    DATASETS to a tile, no dataset's column lies in lane rows, and the
    compiler re-tiled all ten row columns whole inside every launch
    (ten copies of ``s32[4,8,15680,128]``, 256 MB each, at ``mds4``'s 32
    datasets x 2e6 rows a chip: sandbox compile, PERF.md 6, PR 34).
    Resident in lane rows a dataset's column is what the program reads
    (``pad_columns`` pads to whole lane rows only). The segment table
    stays as it is."""
    if name == "chrom_offsets":
        return stacked
    d, n = stacked.shape[:2]
    return stacked.reshape((d, n // LANES, LANES) + stacked.shape[2:])


def _local_query(
    arrays_local, packed, *, window_cap, record_cap, n_iters, axis
):
    """Body run per device: vmap datasets × vmap queries, psum fan-in.
    ``packed`` is the query batch as ``ops.kernel.pack_queries`` lays
    it, replicated."""
    enc = unpack_queries(packed)

    def one_dataset(arrays_one):
        # a column resident in lane rows (lane_rows) is the 1-D column
        # _query_one takes, seen flat: it views it in lane rows again
        arrays_one = {
            k: v
            if k == "chrom_offsets"
            else v.reshape((-1,) + v.shape[2:])
            for k, v in arrays_one.items()
        }
        fn = partial(
            _query_one,
            arrays_one,
            window_cap=window_cap,
            record_cap=record_cap,
            n_iters=n_iters,
        )
        return jax.vmap(fn)(enc)

    per_ds = jax.vmap(one_dataset)(arrays_local)  # leaves: [d_local, B, ...]

    # cross-dataset fan-in: local reduce then one psum over the mesh axis —
    # this collective IS the reference's DynamoDB fanOut counter + poll loop
    agg = {
        "call_count": jax.lax.psum(
            jnp.sum(per_ds["call_count"], axis=0), axis
        ),
        "all_alleles_count": jax.lax.psum(
            jnp.sum(per_ds["all_alleles_count"], axis=0), axis
        ),
        "n_variants": jax.lax.psum(
            jnp.sum(per_ds["n_variants"], axis=0), axis
        ),
        "n_datasets_hit": jax.lax.psum(
            jnp.sum(per_ds["exists"].astype(jnp.int32), axis=0), axis
        ),
        "n_overflow": jax.lax.psum(
            jnp.sum(per_ds["overflow"].astype(jnp.int32), axis=0), axis
        ),
    }
    agg["exists"] = agg["call_count"] > 0
    return per_ds, agg


_FN_CACHE: dict = {}

#: XLA:CPU runs a multi-device mesh as virtual devices rendezvousing on
#: a shared intra-process thread pool; TWO collective programs in
#: flight from different request threads can interleave their
#: per-device rendezvous and deadlock (the forced-host CI mesh, and any
#: CPU fallback deployment). On the chip no guard is taken, and that
#: was run, not assumed (four v5e chips, PR 34): four threads x 30
#: launches of the engine's mesh program at once all returned (1.5 s),
#: and ``mds4.fanout``'s four closed-loop clients launched it
#: concurrently some 800 times a 40 s window with every sampled answer
#: exact and no request failed, on the parent and on the change. So the
#: guard stays CPU-only and free elsewhere.
_CPU_COLLECTIVE_LOCK = threading.Lock()


def _collective_guard():
    if jax.default_backend() == "cpu":
        return _CPU_COLLECTIVE_LOCK
    return contextlib.nullcontext()


def _build_sharded_fn(mesh: Mesh, axis: str, window_cap, record_cap, n_iters):
    key = (mesh, axis, window_cap, record_cap, n_iters)
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    body = partial(
        _local_query,
        window_cap=window_cap,
        record_cap=record_cap,
        n_iters=n_iters,
        axis=axis,
    )
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P()),
    )
    fn = jax.jit(mapped)
    _FN_CACHE[key] = fn
    return fn


def sharded_query(
    stacked_arrays: dict,
    queries,
    *,
    mesh: Mesh,
    n_iters: int,
    axis: str = AXIS,
    window_cap: int = 2048,
    record_cap: int = 1024,
    aggregates_only: bool = False,
    n_datasets: int | None = None,
):
    """Run a query batch against a mesh-sharded dataset stack.

    Returns (per_dataset, aggregates) as numpy: per_dataset leaves are
    [D, B, ...] (D = padded dataset count), aggregates are [B]-shaped
    cross-dataset reductions computed with psum over the mesh.

    ``aggregates_only`` skips fetching the dataset-sharded leaves —
    REQUIRED under multi-controller ``jax.distributed``, where a process
    can only device_get fully-addressable arrays: the psum aggregates
    are replicated (addressable everywhere) while per-dataset results
    live on their owning hosts.

    The encoded batch goes up as ONE packed array
    (``ops.kernel.pack_queries``), put replicated on the mesh's chips in
    one call and counted in ``device.query_uploads{mesh}``; the program
    unpacks it.

    The launch passes the stages every family's does (``kernel.encode``,
    ``kernel.dispatch``: the one upload and the jitted call until it
    returns, ``kernel.readback``: both ``device_get``s, ``kernel.unpack``)
    and is ONE record of the flight recorder under the family ``mesh``:
    ``n_datasets`` real of the stack's padded dataset slots, times the
    batch, are its real and padded pairs, and the bytes both
    ``device_get``s brought back are its ``device.fetched_bytes``.
    """
    from ..telemetry import note_device_stage, record_device_launch
    from ..utils.trace import stage

    with stage("kernel.encode"):
        enc = (
            encode_queries(queries) if isinstance(queries, list) else queries
        )
        b = int(enc["chrom"].shape[0])
        packed = pack_queries(enc)
        fn = _build_sharded_fn(mesh, axis, window_cap, record_cap, n_iters)
    d_pad = int(stacked_arrays["chrom_offsets"].shape[0])
    with _collective_guard():
        with stage("kernel.dispatch") as dispatched:
            # ONE put, replicated on every chip of the mesh: the jitted
            # shard_map finds its P() operand where it wants it
            packed_dev = jax.device_put(packed, NamedSharding(mesh, P()))
            per_ds, agg = fn(stacked_arrays, packed_dev)
        # ONE flight-recorder seam per launch, as every other family's:
        # the engine's mesh program is the family ``mesh``.
        # A pair is one (dataset slot, query); the padding datasets that
        # round the stack up to the mesh are evaluated like the others
        seq = record_device_launch(
            "mesh",
            seam="mesh",
            tier=b,
            specs_real=int(n_datasets or d_pad) * b,
            specs_padded=d_pad * b,
            evaluated_pairs=d_pad * b,
            launch_ms=dispatched.ms,
            uploads=1,
            program_key=(
                "mesh_stack",
                int(mesh.devices.size),
                d_pad,
                tuple(stacked_arrays["pos"].shape[1:]),
                n_iters,
                b,
                window_cap,
                record_cap,
            ),
        )
        with stage("kernel.readback") as read_back:
            agg = jax.device_get(agg)
            per_ds = {} if aggregates_only else jax.device_get(per_ds)
    with stage("kernel.unpack"):
        per_out = {k: np.asarray(v) for k, v in per_ds.items()}
        agg_out = {k: np.asarray(v) for k, v in agg.items()}
        note_device_stage(
            seq,
            fetch_ms=read_back.ms,
            fetch_bytes=sum(
                v.nbytes for v in (*per_out.values(), *agg_out.values())
            ),
        )
    return per_out, agg_out


def aggregate_struct(agg: dict) -> dict:
    """Human-readable summary of the psum aggregates for one query.

    ``n_overflow`` > 0 means at least one dataset's candidate window was
    truncated at window_cap: the aggregates are then lower bounds and the
    caller must re-answer those datasets on host (engine.host_match_rows),
    exactly like the single-device engine's overflow fallback.
    """
    return {
        "exists": bool(agg["exists"]),
        "call_count": int(agg["call_count"]),
        "all_alleles_count": int(agg["all_alleles_count"]),
        "n_variants": int(agg["n_variants"]),
        "n_datasets_hit": int(agg["n_datasets_hit"]),
        "n_overflow": int(agg["n_overflow"]),
        "exact": int(agg["n_overflow"]) == 0,
    }
