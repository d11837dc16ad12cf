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
import os
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.columnar import N_CHROM_CODES, VariantIndexShard
from ..ops.kernel import (
    LANES,
    DeviceIndex,
    QueryResults,
    _donate_uploads,
    _query_one,
    _quiet_donation,
    active_ladder,
    bisect_iters,
    encode_queries,
    pack_queries,
    pad_columns,
    pad_shard_columns,
    padded_rows,
    unpack_queries,
    window_hint_for,
)

AXIS = "d"


def __getattr__(name: str):
    """Module back-compat properties (PEP 562), served by the device
    flight recorder (telemetry.py): the old unlocked module-global
    increments raced across request threads on real accelerators
    (no ``_CPU_COLLECTIVE_LOCK`` there); the recorder's lock now owns
    them and these names stay readable for tests.

    - ``N_LAUNCHES``: compiled mesh-program dispatches (one per jitted
      sharded/fused query-batch launch) — the perf_smoke evidence that
      the pod tier really is single-launch; kernel.py N_LAUNCHES and
      scatter_kernel.N_DISPATCHES count the single-device families.
    - ``N_SLICED_LAUNCHES``: launches that ran the per-device SLICED
      batch layout (the encoded batch sharded by owning device).
    - ``N_EVALUATED_PAIRS``: per-device FLOP proxy — evaluated
      (device, query-slot) pairs summed over the mesh, per launch
      (replicated layout evaluates batch x n_dev pairs, the sliced
      layout ~batch total). The structural scaling assert of
      tests/test_mesh_dispatch.py reads this instead of wall-clock
      (virtual-CPU honesty rule).
    """
    from ..telemetry import flight_recorder

    if name == "N_LAUNCHES":
        return flight_recorder.mesh_launches
    if name == "N_SLICED_LAUNCHES":
        return flight_recorder.sliced_launches
    if name == "N_EVALUATED_PAIRS":
        return flight_recorder.evaluated_pairs
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )


def _slice_default() -> bool:
    """Process default for per-device batch slicing (BEACON_MESH_SLICE;
    on unless explicitly disabled). MeshFusedIndex instances built by
    the dispatch tier carry the config-resolved value instead."""
    from ..config import ENV_OFF

    return os.environ.get("BEACON_MESH_SLICE", "1").lower() not in ENV_OFF


#: LEGACY per-device slice shape tiers, kept as the documented
#: baseline: live slice-tier selection consults
#: ``kernel.active_ladder().slice_rungs`` (the process TierLadder with
#: a 1-floor — ISSUE 17), so batch padding and slice padding can never
#: drift onto different ladders. Still a bounded set either way, so
#: the compiled-program cache stays a handful of shapes per config.
SLICE_TIERS = (1, 8, 64, 512, 2048)


def _owner_default() -> bool:
    """Process default for owner-sharded mesh outputs
    (BEACON_MESH_OWNER_OUTPUTS; on unless explicitly disabled).
    MeshFusedIndex instances built by the dispatch tier carry the
    config-resolved value instead."""
    from ..config import ENV_OFF

    return os.environ.get(
        "BEACON_MESH_OWNER_OUTPUTS", "1"
    ).lower() not in ENV_OFF


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


def _plane_reduce(
    flags_r,
    ac_r,
    an_r,
    rec_r,
    gt,
    gt2,
    tok1,
    tok2,
    valid,
    *,
    has_counts,
    use_counts=None,
):
    """The per-query masked-plane reduction of the fused mesh program
    (:func:`_local_fused_query`): per-row masked popcounts, the
    record-segmented selected call/allele counts, and the sample-hit OR
    over the exact ``record-cumulative > 0`` row subset (the same
    ``grp >= k0`` selection materialize_response uses).

    Inputs are batch-leading: ``flags_r``/``ac_r``/``an_r``/``rec_r``
    [B, R] row gathers, ``gt``/``gt2``/``tok1``/``tok2`` [B, R, W]
    plane gathers ALREADY AND-masked with each query's sample mask
    (``gt2``/``tok*`` may be None when ``has_counts`` is False),
    ``valid`` [B, R] the real-row mask. ``use_counts`` is an optional
    [B] bool switch: False rows take the INFO-column ac/an semantics
    (the extraction-shape contract, where materialize reads the
    columns and only consumes ``or_words``); None means all-True (the
    selected-samples restricted counting). Ploidy>2 saturation side-tables are
    host-only — materialize adds those extras on top of the saturated
    popcounts, and rc POSITIVITY (hence k0 and the OR subset) is
    extras-invariant.
    """
    from ..index.columnar import FLAG

    pcw = lambda x: jnp.sum(
        jax.lax.population_count(x), axis=-1
    ).astype(jnp.int32)
    if has_counts:
        pc_call = pcw(gt) + pcw(gt2)
        pc_tok = pcw(tok1) + pcw(tok2)
        use_gt = (flags_r & FLAG.AC_INFO) == 0
        use_an = (flags_r & FLAG.AN_INFO) == 0
        if use_counts is not None:
            use_gt = use_gt & use_counts[:, None]
            use_an = use_an & use_counts[:, None]
        rc = jnp.where(use_gt, pc_call, ac_r)
        an_eff = jnp.where(use_an, pc_tok, an_r)
    else:
        pc_call = jnp.zeros_like(ac_r)
        pc_tok = jnp.zeros_like(ac_r)
        rc = ac_r
        an_eff = an_r
    rc = rc * valid
    call_count = jnp.sum(rc, axis=1)

    # record boundaries among the (sorted, -1-tail-padded) matched
    # rows: padding lanes clip to row 0, whose rec_id can ALIAS a
    # real matched record — give invalid lanes an impossible id so
    # segment boundaries never cross the valid/padding edge
    rec_eff = jnp.where(valid, rec_r, jnp.int32(-2))
    first = valid & jnp.concatenate(
        [
            jnp.ones_like(valid[:, :1]),
            rec_eff[:, 1:] != rec_eff[:, :-1],
        ],
        axis=1,
    )
    alleles = jnp.sum(jnp.where(first, an_eff, 0), axis=1)

    # sample-hit OR over materialize_response's exact grp >= k0 row
    # subset: a row participates iff the cumulative rc BEFORE its
    # record (base) is positive, or ANY row of its own record has
    # rc > 0. Both come from segmented prefix scans (the flipped
    # pass covers 'positive rc later in my record').
    c = jnp.cumsum(rc, axis=1)
    before = c - rc
    base = jax.lax.cummax(
        jnp.where(first, before, jnp.int32(-1)), axis=1
    )
    fwd_any = (c - base) > 0  # rc>0 at-or-before me, in my record
    rc_f = jnp.flip(rc, axis=1)
    first_f = jnp.flip(valid, axis=1) & jnp.concatenate(
        [
            jnp.ones_like(valid[:, :1]),
            jnp.flip(rec_eff, axis=1)[:, 1:]
            != jnp.flip(rec_eff, axis=1)[:, :-1],
        ],
        axis=1,
    )
    c_f = jnp.cumsum(rc_f, axis=1)
    base_f = jax.lax.cummax(
        jnp.where(first_f, c_f - rc_f, jnp.int32(-1)), axis=1
    )
    bwd_any = jnp.flip((c_f - base_f) > 0, axis=1)
    or_sel = valid & ((base > 0) | fwd_any | bwd_any)
    or_words = jax.lax.reduce(
        jnp.where(or_sel[:, :, None], gt, jnp.int32(0)),
        np.int32(0),
        jax.lax.bitwise_or,
        dimensions=(1,),
    )  # [B, W]
    return {
        "call_count": call_count,
        "all_alleles_count": alleles,
        "or_words": or_words,
        "pc_call": pc_call * valid,
        "pc_tok": pc_tok * valid,
    }


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
        # the engine's mesh program is the family ``mesh`` (the pod
        # tier's run_mesh_queries keeps mesh_replicated / mesh_sliced).
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


class MeshPendingResults:
    """Pending handle for a mesh launch (the micro-batcher's
    launch/fetch overlap contract, like
    :class:`ops.kernel.PendingQueryResults`).

    ``positions`` is the sliced layout's slot map (query j's results
    live at slot ``positions[j]`` of the owner-sorted padded batch):
    :meth:`fetch` applies the inverse permute so callers see their
    original order; None means the replicated layout (trim to the
    first ``b`` rows). Plane outputs (``pc_call``/``pc_tok``/
    ``or_words``) ride along when the launch ran the plane program.

    ``owner_layout`` non-None means the launch returned OWNER-SHARDED
    outputs (``out_specs P(axis)`` — the output diet, ISSUE 17):
    device g holds slots ``[g*c_slot, (g+1)*c_slot)`` and only the
    first ``counts[g]`` carry real queries. :meth:`fetch` then pulls
    each owner's real rows directly off its shard — the bytes crossing
    device->host are ~the real batch, not ``n_dev*c_slot`` padded
    slots — and asserts it never materialises a full-size replica."""

    __slots__ = ("_out", "_b", "_pos", "_owner", "flight_seq")

    def __init__(self, out, b: int, positions=None,
                 flight_seq: int | None = None, owner_layout=None):
        self._out = out
        self._b = b
        self._pos = positions
        #: (n_dev, c_slot, counts[n_dev]) under owner-sharded outputs
        self._owner = owner_layout
        #: the launch's flight-recorder record (fetch-stage timing)
        self.flight_seq = flight_seq

    @staticmethod
    def _fetch_device(a):
        """The explicit fetch device for a replicated output leaf: the
        lowest-id addressable device. ``jax.device_get`` on a fully
        replicated array reads shard 0 *by convention*; making the
        choice explicit here keeps the fetch path auditable (and
        stable if the runtime's shard ordering ever changes)."""
        shards = getattr(a, "addressable_shards", None)
        if not shards:
            return None
        return min(
            shards, key=lambda s: getattr(s.device, "id", 0)
        ).data

    def _host_replicated(self) -> dict:
        """One replica per leaf, from the explicit fetch device."""
        picked = {}
        for k, a in self._out.items():
            data = self._fetch_device(a)
            picked[k] = a if data is None else data
        return jax.device_get(picked)

    def _host_owner_sharded(self):
        """Each owner's real rows, straight off its shard.

        Returns ``(host, sel_idx)``: host leaves are the counts-trimmed
        owner blocks concatenated in owner order (``sum(counts)``
        rows), and ``sel_idx[j]`` is query j's row in that compact
        layout."""
        n_dev, c_slot, counts = self._owner
        host = {}
        for k, a in self._out.items():
            shards = getattr(a, "addressable_shards", None)
            # single-controller contract (ROADMAP item 1): every
            # output shard is addressable from this process
            assert shards is not None and len(shards) == n_dev, (
                "owner-sharded fetch needs all output shards "
                "addressable (single-controller pod)"
            )
            blocks = sorted(
                shards, key=lambda s: s.index[0].start or 0
            )
            parts = []
            for g, sh in enumerate(blocks):
                # the output diet's invariant: each device holds ONLY
                # its own c_slot-slot block — a full-size (replicated)
                # shard here would mean the program regressed to
                # reassembling every device's output
                assert sh.data.shape[0] == c_slot, (
                    f"owner-sharded output leaf {k!r} materialised a "
                    f"{sh.data.shape[0]}-slot shard (want {c_slot})"
                )
                parts.append(sh.data[: int(counts[g])])
            host[k] = parts
        host = jax.device_get(host)
        host = {k: np.concatenate(v) for k, v in host.items()}
        starts = np.concatenate(([0], np.cumsum(counts)))[:-1]
        pos = np.asarray(self._pos)
        sel_idx = starts[pos // c_slot] + pos % c_slot
        return host, sel_idx

    def fetch(self) -> QueryResults:
        from ..telemetry import note_device_stage

        t0 = time.perf_counter()
        if self._owner is not None:
            out, sel_idx = self._host_owner_sharded()
            sel = lambda a: np.asarray(a)[sel_idx]
        else:
            out = self._host_replicated()
            if self._pos is None:
                sel = lambda a: np.asarray(a)[: self._b]
            else:
                sel = lambda a: np.asarray(a)[self._pos]
        note_device_stage(
            self.flight_seq,
            fetch_ms=(time.perf_counter() - t0) * 1e3,
            fetch_bytes=sum(
                np.asarray(v).nbytes for v in out.values()
            ),
        )
        self._out = None  # free the device buffers promptly
        extra = {
            k: sel(out[k])
            for k in ("pc_call", "pc_tok", "or_words")
            if k in out
        }
        return QueryResults(
            exists=sel(out["exists"]),
            call_count=sel(out["call_count"]),
            n_variants=sel(out["n_variants"]),
            all_alleles_count=sel(out["all_alleles_count"]),
            n_matched=sel(out["n_matched"]),
            overflow=sel(out["overflow"]),
            rows=sel(out["rows"]),
            **extra,
        )


class MeshFusedIndex:
    """The fused stacked index (``ops.kernel.FusedDeviceIndex`` layout:
    contiguous per-shard row spans + a per-shard chromosome segment
    table), sharded over a 1-D device mesh.

    Datasets are grouped round-robin-contiguously: device g owns shards
    ``[g*d_local, (g+1)*d_local)`` as ONE FusedDeviceIndex-style block —
    columns concatenated to a common padded row count, segment table
    ``[d_local, 27]``, and a ``seg_base`` row-offset table mapping
    block-absolute row ids back to dataset-local ids. The whole stack is
    device_put once with ``NamedSharding(P(axis))`` on the leading
    device axis, so each device holds only its own block (the property
    that lets a 1000-Genomes-scale plane-less index spread across a pod
    instead of duplicating onto one chip like the single-device fused
    stack).

    :meth:`run_mesh_queries` then answers a batch of (shard, query)
    pairs in ONE compiled shard_map launch. Under the default SLICED
    layout the encoded batch itself is sharded by owning device
    (owner-sorted permute, per-device counts padded to a shared
    ``SLICE_TIERS`` tier), so each device evaluates ONLY the queries
    targeting its shards — ~1/n_dev the per-device bisect/predicate
    work; the replicated layout (``slice_batch=False``) keeps every
    device running the full batch masked by ownership. Either way,
    scalar aggregates fan in with ``psum`` and the record-granularity
    hit rows gather through ``ops.gather_kernel`` — a Pallas
    ``make_async_remote_copy`` ring on TPU, ``all_gather``+sum
    elsewhere. Row ids come back DATASET-LOCAL (the program subtracts
    ``seg_base`` on device), so materialisation needs no
    ``to_local_rows`` remap. Built ``with_planes=True``, the genotype
    planes stack group-wise with their datasets and plane-reading
    query shapes ride the same launch with per-query sample masks.

    The serving micro-batcher treats this index exactly like a
    FusedDeviceIndex: ``submit_many(index, specs, shard_ids=...)``
    coalesces concurrent queries for different datasets into the same
    single launch (``ops.run_queries_auto`` dispatches on the
    ``run_mesh_queries`` attribute).

    Staleness contract (ingest-while-serving): the stack is built from
    a BASE shard snapshot and keyed on the engine's
    ``base_fingerprint()`` — delta-shard publishes leave both
    untouched, so a standing tail never cold-starts this index; only a
    compaction or re-ingest (a base publish) makes it stale. The owner
    (``MeshDispatchTier`` / the engine's mesh state) serves the delta
    tail per-shard on host next to the single mesh launch.
    """

    PAD_UNIT = DeviceIndex.PAD_UNIT

    def __init__(
        self,
        shards: list[VariantIndexShard],
        mesh: Mesh,
        *,
        axis: str = AXIS,
        pad_unit: int | None = None,
        with_planes: bool = False,
        slice_batch: bool | None = None,
        owner_outputs: bool | None = None,
    ):
        from ..index.columnar import stack_shard_columns

        if not shards:
            raise ValueError("MeshFusedIndex needs at least one shard")
        self.mesh = mesh
        self.axis = axis
        #: per-device batch slicing default for run_mesh_queries
        #: (None = the BEACON_MESH_SLICE process default at call time)
        self.slice_batch = slice_batch
        #: owner-sharded output default for run_mesh_queries (None =
        #: the BEACON_MESH_OWNER_OUTPUTS process default at call time)
        self.owner_outputs = owner_outputs
        n_dev = int(mesh.devices.size)
        d = len(shards)
        d_local = -(-d // n_dev)  # shards per device, last groups may pad
        self.n_dev = n_dev
        self.d_local = d_local
        self.n_shards = d

        groups = [
            shards[g * d_local : (g + 1) * d_local] for g in range(n_dev)
        ]
        stacked = []  # (cols, offsets[k,27], base[k+1]) per group
        n_rows_per_group = []
        for grp in groups:
            if grp:
                cols, offs, base = stack_shard_columns(grp)
                stacked.append((cols, offs, base))
                n_rows_per_group.append(int(base[-1]))
            else:
                stacked.append(None)
                n_rows_per_group.append(0)
        n_pad = padded_rows(max(n_rows_per_group), pad_unit or self.PAD_UNIT)
        # empty trailing groups (D < n_dev*d_local) reuse group 0's
        # column dtypes; their zero chrom_offsets make every row span
        # empty, so no query can reach the pad rows
        proto_cols = stacked[0][0]
        names = list(proto_cols)
        per_group_arrays = []
        offsets = np.zeros((n_dev, d_local, N_CHROM_CODES + 1), np.int32)
        seg_base = np.zeros((n_dev, d_local), np.int32)
        for g, entry in enumerate(stacked):
            if entry is None:
                empty = {
                    k: np.empty((0,) + v.shape[1:], v.dtype)
                    for k, v in proto_cols.items()
                }
                per_group_arrays.append(pad_columns(empty, 0, n_pad))
                continue
            cols, offs, base = entry
            k = offs.shape[0]
            per_group_arrays.append(
                pad_columns(cols, n_rows_per_group[g], n_pad)
            )
            offsets[g, :k] = offs
            seg_base[g, :k] = base[:k].astype(np.int32)
        host_arrays = {
            name: np.stack([p[name] for p in per_group_arrays])
            for name in names
        }
        host_arrays["chrom_offsets"] = offsets

        # genotype planes, group-stacked WITH their index rows (the
        # engine's StackedIndex layout folded into the fused tier):
        # device g holds the concatenated plane rows of the shards it
        # owns, padded to the common group row count and the widest
        # shard's word width — the plane-shape queries (selected
        # samples / sample extraction) then ride the same single
        # launch as the match shapes, masks travelling per query.
        self.plane_words = 0
        self.has_planes = False
        self.has_count_planes = False
        if with_planes and all(s.gt_bits is not None for s in shards):
            W = max(s.gt_bits.shape[1] for s in shards)
            self.plane_words = W
            self.has_planes = True
            self.has_count_planes = all(
                s.has_count_planes for s in shards
            )

            def stackp(attr):
                # fill one preallocated block (concatenate + stack
                # would transiently double the multi-GB host footprint
                # of a 1000-Genomes plane set, like StackedIndex)
                out = np.zeros((n_dev, n_pad, W), np.uint32)
                for g, grp in enumerate(groups):
                    r0 = 0
                    for sh in grp:
                        a = getattr(sh, attr)
                        out[g, r0 : r0 + a.shape[0], : a.shape[1]] = a
                        r0 += a.shape[0]
                return out.view(np.int32)

            host_arrays["plane_gt"] = stackp("gt_bits")
            if self.has_count_planes:
                host_arrays["plane_gt2"] = stackp("gt_bits2")
                host_arrays["plane_tok1"] = stackp("tok_bits1")
                host_arrays["plane_tok2"] = stackp("tok_bits2")
        #: per-device HBM the stacked planes occupy (0 when not
        #: stacked) — what the owner registers against the engine's
        #: plane budget ledger so later uploads see this allocation
        self.plane_bytes_device = (
            self.plane_bytes_per_device(
                shards, n_dev=n_dev, pad_unit=pad_unit or self.PAD_UNIT
            )
            if self.has_planes
            else 0
        )

        sharding = NamedSharding(mesh, P(axis))
        self.arrays = {
            k: jax.device_put(jnp.asarray(v), sharding)
            for k, v in host_arrays.items()
        }
        self.seg_base = jax.device_put(jnp.asarray(seg_base), sharding)
        self.n_padded = n_pad
        self.n_iters = bisect_iters(n_pad)
        #: ragged-window bound (ISSUE 17): the widest (shard,
        #: chromosome) segment across every device's block —
        #: run_mesh_queries clamps its window_cap to this, so
        #: record-heavy launches stop paying the engine-wide gather
        #: width (never adds an overflow; see kernel.window_hint_for)
        self.window_hint = window_hint_for(offsets)

    @classmethod
    def plane_bytes_per_device(
        cls,
        shards,
        *,
        n_dev: int,
        pad_unit: int | None = None,
    ) -> int:
        """Per-device HBM bytes the group-stacked genotype planes will
        occupy (incl. group row padding, widest-shard W lane-rounded,
        and the count-plane multiplicity). The dispatch tier's plane
        budget gate asks THIS instead of re-deriving the allocation
        math, so gate and ``stackp`` can never drift — the
        ``StackedIndex.plane_bytes_per_device`` contract for the fused
        layout."""
        if not shards or any(s.gt_bits is None for s in shards):
            return 0
        d_local = -(-len(shards) // n_dev)
        groups = [
            shards[g * d_local : (g + 1) * d_local] for g in range(n_dev)
        ]
        rows = max(sum(s.n_rows for s in g) for g in groups)
        n_pad = padded_rows(rows, pad_unit or cls.PAD_UNIT)
        W = max(s.gt_bits.shape[1] for s in shards)
        w_lane = -(-W // 128) * 128  # XLA minor-dim lane tiling
        n_planes = 4 if all(s.has_count_planes for s in shards) else 1
        return n_pad * w_lane * 4 * n_planes

    def shard_id(self, position: int) -> int:
        """Global shard id for the ``position``-th shard of the build
        list: device ``position // d_local``, local slot ``% d_local``
        — contiguous by construction, so this is the identity; kept as
        the one documented mapping in case the grouping ever changes."""
        return position

    def _slice_layout(self, enc, masks, use_counts):
        """Owner-sorted sliced layout: permute the encoded batch so
        device g's queries occupy slots ``[g*C, g*C+count_g)`` of a
        ``[n_dev*C]`` array (C = the largest per-device count padded to
        a shared tier of the process ladder's ``slice_rungs``, so the
        compiled-program cache stays a handful of per-device shapes).
        Padding slots carry an inert filler (chrom code 0 — its row
        span is empty in every shard — targeted at the slot's own
        device group, so the filler never crosses an ownership
        boundary); their output positions are simply never read back.
        Returns the padded
        ``(enc, masks, use_counts, positions, counts, c_slot)`` where
        ``positions[j]`` is query j's slot — the inverse permute
        applied at fetch — and ``counts[g]`` is device g's real query
        count (the owner-sharded fetch's trim bound)."""
        shard = np.asarray(enc["shard"])
        b = shard.shape[0]
        owner = shard // self.d_local
        counts = np.bincount(owner, minlength=self.n_dev)
        cmax = int(counts.max())
        slice_rungs = active_ladder().slice_rungs
        c_slot = next((t for t in slice_rungs if cmax <= t), cmax)
        order = np.argsort(owner, kind="stable")
        starts = np.concatenate(([0], np.cumsum(counts)))[:-1]
        ranks = np.arange(b, dtype=np.int64) - np.repeat(starts, counts)
        pos = np.empty(b, dtype=np.int64)
        pos[order] = owner[order] * c_slot + ranks
        total = self.n_dev * c_slot
        out = {}
        for k, v in enc.items():
            if k == "shard":
                # filler slots target their own device's first local
                # shard slot (always owned; chrom 0 keeps them inert)
                arr = np.repeat(
                    np.arange(self.n_dev, dtype=np.int32)
                    * np.int32(self.d_local),
                    c_slot,
                )
            else:
                arr = np.zeros((total,) + v.shape[1:], v.dtype)
            arr[pos] = v
            out[k] = arr
        if masks is not None:
            m = np.zeros((total, masks.shape[1]), masks.dtype)
            m[pos] = masks
            masks = m
            uc = np.zeros(total, np.bool_)
            uc[pos] = use_counts
            use_counts = uc
        return out, masks, use_counts, pos, counts, c_slot

    def run_mesh_queries(
        self,
        queries,
        *,
        window_cap: int = 2048,
        record_cap: int = 1024,
        async_fetch: bool = False,
        sample_masks=None,
        mask_counts=None,
        slice_batch: bool | None = None,
        owner_outputs: bool | None = None,
    ):
        """ONE compiled launch answering a (shard, query)-pair batch.

        ``queries``: a pre-encoded dict (``encode_queries`` with
        ``shard_ids``). A bare list is a LOUD error: the old implicit
        ``shard_ids=[0]*n`` silently answered every query against
        shard 0's row span — callers must say which shard each query
        targets. Returns :class:`ops.kernel.QueryResults` (or the
        pending handle under ``async_fetch`` — the micro-batcher's
        launch/fetch overlap contract), with ``rows`` already
        dataset-local.

        ``sample_masks`` (uint32 [B, W], W = ``plane_words``) arms the
        genotype-plane program: each query's matched rows reduce under
        ITS mask on the owning device, and the results carry
        ``pc_call`` / ``pc_tok`` / ``or_words`` for
        ``materialize_response(fused=...)``. ``mask_counts`` ([B]
        bool) switches a query to genotype-derived restricted counting
        (the selected-samples leaf) instead of the INFO-column ac/an
        (the extraction shapes).

        ``slice_batch`` (default: the index's config, else
        ``BEACON_MESH_SLICE``) shards the encoded batch by owning
        device — an owner-sorted permute with per-device counts padded
        to a shared tier — so each device evaluates only the queries
        targeting its shards (~1/n_dev the per-device work) instead of
        the full replicated batch masked by ownership. The psum fan-in
        and ring row-gather reassemble, and the inverse permute
        restores caller order at fetch.

        ``owner_outputs`` (default: the index's config, else
        ``BEACON_MESH_OWNER_OUTPUTS``; sliced layout only) keeps the
        outputs OWNER-SHARDED (``out_specs P(axis)``): the sliced
        layout routes every query — and every inert filler — to
        exactly one owning device, so no output needs a cross-device
        combine at all. The program skips the psum fan-in AND the ring
        row-gather (the ``gather_partials_many`` combine remains only
        for the replicated layout and the StackedIndex paths, which
        genuinely reduce across devices), and :meth:`fetch` pulls each
        owner's real rows directly instead of one full-size replica —
        the fetched bytes and the ring pass both shrink ~1/n_dev."""
        if isinstance(queries, list):
            raise ValueError(
                "MeshFusedIndex batches must carry explicit shard ids "
                "(encode_queries(..., shard_ids=...)): a bare list "
                "would silently target shard 0, which can only answer "
                "for its own row span"
            )
        enc = queries
        if "shard" not in enc:
            raise ValueError(
                "MeshFusedIndex batches must carry shard ids "
                "(encode_queries(..., shard_ids=...))"
            )
        with_planes = sample_masks is not None
        if with_planes and not self.has_planes:
            raise ValueError(
                "sample_masks passed but this stack carries no "
                "genotype planes (built with_planes=False)"
            )
        b = int(enc["chrom"].shape[0])
        # ragged-window clamp at the one choke point (warmup and
        # serving both route through here, so the compiled window
        # shape can never differ between them)
        window_cap = min(window_cap, self.window_hint)
        use_slice = (
            slice_batch
            if slice_batch is not None
            else (
                self.slice_batch
                if self.slice_batch is not None
                else _slice_default()
            )
        )
        use_slice = bool(use_slice) and self.n_dev > 1 and b > 0
        owner_out = (
            owner_outputs
            if owner_outputs is not None
            else (
                self.owner_outputs
                if self.owner_outputs is not None
                else _owner_default()
            )
        )
        # owner-sharded outputs require the sliced layout: only there
        # is every query (and filler) single-owner by construction
        owner_out = bool(owner_out) and use_slice
        masks = None
        use_counts = None
        if with_planes:
            masks = np.ascontiguousarray(
                np.asarray(sample_masks, np.uint32)
            ).view(np.int32)
            use_counts = (
                np.asarray(mask_counts, np.bool_)
                if mask_counts is not None
                else np.zeros(b, np.bool_)
            )
            if not self.has_count_planes:
                # no gt2/tok planes in the stack: restricted counting
                # must come from the host path, never a zero plane
                use_counts = np.zeros(b, np.bool_)
        pos = None
        owner_layout = None
        if use_slice:
            enc, masks, use_counts, pos, counts, c_slot = (
                self._slice_layout(enc, masks, use_counts)
            )
            local_b = int(enc["chrom"].shape[0]) // self.n_dev
            if owner_out:
                owner_layout = (self.n_dev, c_slot, counts)
        else:
            tier = active_ladder().tier_for(b)
            if b and tier and tier != b:
                enc = {
                    k: np.concatenate(
                        [v, np.repeat(v[:1], tier - b, axis=0)]
                    )
                    for k, v in enc.items()
                }
                if masks is not None:
                    masks = np.concatenate(
                        [masks, np.repeat(masks[:1], tier - b, axis=0)]
                    )
                    use_counts = np.concatenate(
                        [use_counts, np.zeros(tier - b, np.bool_)]
                    )
            local_b = int(enc["chrom"].shape[0])
        from ..ops.gather_kernel import default_impl

        gather_impl = default_impl()
        donate = _donate_uploads()
        key = (
            "mesh_fused",
            self.mesh,
            self.axis,
            window_cap,
            record_cap,
            self.n_iters,
            self.d_local,
            self.n_dev,
            gather_impl,
            use_slice,
            with_planes,
            self.has_count_planes if with_planes else False,
            owner_out,
            donate,
        )
        fn = _FN_CACHE.get(key)
        if fn is None:
            kw = dict(
                window_cap=window_cap,
                record_cap=record_cap,
                n_iters=self.n_iters,
                axis=self.axis,
                d_local=self.d_local,
                n_dev=self.n_dev,
                gather_impl=gather_impl,
                sliced=use_slice,
                has_counts=self.has_count_planes,
                owner_out=owner_out,
            )
            if with_planes:
                body = lambda a, sb, e, m, uc: _local_fused_query(
                    a, sb, e, m, uc, **kw
                )
                extra_specs = (
                    (P(self.axis), P(self.axis))
                    if use_slice
                    else (P(), P())
                )
                donate_nums = (2, 3, 4)
            else:
                body = lambda a, sb, e: _local_fused_query(
                    a, sb, e, None, None, **kw
                )
                extra_specs = ()
                donate_nums = (2,)
            enc_spec = P(self.axis) if use_slice else P()
            mapped = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(self.axis), P(self.axis), enc_spec)
                + extra_specs,
                # owner-sharded outputs stay on their owning device
                # (the output diet); otherwise the outputs ARE
                # replicated (psum / full ring gather)
                out_specs=P(self.axis) if owner_out else P(),
                # axis_index-driven ownership masking defeats the
                # replication checker either way
                check_vma=False,
            )
            # donate the per-launch upload buffers (encode dict +
            # plane masks; the persistent index arrays at args 0-1 are
            # never donated) — steady-state serving stops
            # double-buffering every encode batch in HBM
            fn = (
                jax.jit(mapped, donate_argnums=donate_nums)
                if donate
                else jax.jit(mapped)
            )
            _FN_CACHE[key] = fn
        from ..telemetry import record_device_launch
        from ..utils.trace import graft_launch_span, span

        family = (
            "plane"
            if with_planes
            else ("mesh_sliced" if use_slice else "mesh_replicated")
        )
        with span("mesh.run_queries") as sp:
            t0 = time.perf_counter()
            if use_slice:
                sharding = NamedSharding(self.mesh, P(self.axis))
                put = lambda v: jax.device_put(jnp.asarray(v), sharding)
            else:
                put = jnp.asarray
            enc_dev = {k: put(v) for k, v in enc.items()}
            args = (self.arrays, self.seg_base, enc_dev)
            if with_planes:
                args = args + (put(masks), put(use_counts))
            with _collective_guard(), _quiet_donation():
                out = fn(*args)
                if jax.default_backend() == "cpu":
                    # the guard must cover the EXECUTION, not just the
                    # dispatch: block before releasing so a pipelined
                    # fetch (or the next launch) can't overlap this
                    # program's device rendezvous
                    out = jax.block_until_ready(out)
            launch_ms = (time.perf_counter() - t0) * 1e3
            # the one flight-recorder seam for every mesh launch:
            # replicated layouts pad the whole batch to its tier on
            # every device, sliced layouts pad each device's slice to
            # the shared slice tier — either way the padded slot count
            # is local_b x n_dev, the evaluated-pairs FLOP proxy
            seq = record_device_launch(
                family,
                seam="mesh",
                tier=local_b,
                specs_real=b,
                specs_padded=(
                    local_b * self.n_dev if use_slice else local_b
                ),
                evaluated_pairs=local_b * self.n_dev,
                launch_ms=launch_ms,
                sliced=use_slice,
                donated=(len(enc_dev) + (2 if with_planes else 0))
                if donate
                else 0,
                program_key=(
                    "mesh",
                    self.n_dev,
                    self.d_local,
                    self.n_iters,
                    self.n_padded,
                    self.plane_words if with_planes else 0,
                    gather_impl,
                    use_slice,
                    with_planes,
                    self.has_count_planes if with_planes else False,
                    local_b,
                    window_cap,
                    record_cap,
                    # owner-sharded and donated variants are distinct
                    # compiled programs (out_specs / donate_argnums)
                    "own" if owner_out else "repl",
                    "don" if donate else "nodon",
                ),
            )
            sp.note(
                batch=b,
                mesh=self.n_dev,
                sliced=use_slice,
                planes=with_planes,
            )
            graft_launch_span(
                sp,
                elapsed_ms=launch_ms,
                family=family,
                tier=local_b,
                specs=b,
            )
        pending = MeshPendingResults(
            out, b, pos, seq, owner_layout=owner_layout
        )
        return pending if async_fetch else pending.fetch()


def _local_fused_query(
    arrays_local,
    seg_base_local,
    enc,
    masks,
    use_counts,
    *,
    window_cap,
    record_cap,
    n_iters,
    axis,
    d_local,
    n_dev,
    gather_impl,
    sliced,
    has_counts,
    owner_out=False,
):
    """Per-device body of the pod-local fused program.

    Replicated layout (``sliced=False``): every device runs the full
    batch, answers the queries whose target shard it owns, zeros the
    rest. Sliced layout: the batch arrives SHARDED over the mesh axis
    (owner-sorted, per-device counts padded to a shared tier), so each
    device evaluates only its own slice — ~1/n_dev the per-device
    bisect/predicate work — and scatters its block into the global
    slot range before the same psum fan-in / ring row-gather
    reassemble replicated outputs.

    ``owner_out=True`` (sliced only — the output diet, ISSUE 17)
    skips BOTH combines: every local query is owned by construction,
    so each device just returns its own [C]-block (rows already
    rebased dataset-local, plane reductions local) and the outputs
    leave the program owner-sharded (``out_specs P(axis)``) — no
    psum, no ring pass, nothing replicated.

    ``masks``/``use_counts`` non-None arm the genotype-plane path:
    matched rows reduce under each query's own sample mask on the
    owning device (:func:`_plane_reduce`), and pc_call/pc_tok/or_words
    ride the row gather — ONE combined ring pass for all four blocks.
    """
    from ..ops.gather_kernel import gather_partials, gather_partials_many

    plane_names = ("plane_gt", "plane_gt2", "plane_tok1", "plane_tok2")
    arrs = {
        k: v[0] for k, v in arrays_local.items() if k not in plane_names
    }
    seg_base = seg_base_local[0]  # [d_local]
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    sid = enc["shard"] - me * jnp.int32(d_local)
    owned = (sid >= 0) & (sid < d_local)
    q = dict(enc)
    q["shard"] = jnp.clip(sid, 0, d_local - 1)
    res = jax.vmap(
        partial(
            _query_one,
            arrs,
            window_cap=window_cap,
            record_cap=record_cap,
            n_iters=n_iters,
        )
    )(q)
    own_i = owned.astype(jnp.int32)
    c = int(enc["chrom"].shape[0])  # local batch (global/n_dev if sliced)

    if sliced and owner_out:
        # the output diet: every local query (and filler) is owned by
        # construction, so the local [C]-block IS the final answer for
        # these slots — no psum, no ring gather, outputs stay on their
        # owning device (out_specs P(axis)). Ownership masking is kept
        # as a structural-zero guard for any slot that could ever
        # arrive misrouted.
        mask = lambda x: x * _bcast(own_i, x)
        agg = {
            k: mask(res[k])
            for k in (
                "call_count",
                "n_variants",
                "all_alleles_count",
                "n_matched",
            )
        }
        agg["overflow"] = res["overflow"] & owned
        agg["exists"] = agg["call_count"] > 0
        rows = res["rows"]
        agg["rows"] = jnp.where(
            (rows >= 0) & owned[:, None],
            rows - seg_base[q["shard"]][:, None],
            jnp.int32(-1),
        )
        if masks is None:
            return agg
        rows_abs = res["rows"]
        valid = rows_abs >= 0
        n = arrs["pos"].shape[0]
        safe = jnp.clip(rows_abs, 0, n - 1)
        m = masks[:, None, :]  # [C, 1, W]
        gt = arrays_local["plane_gt"][0][safe] & m  # [C, R, W]
        pr = _plane_reduce(
            arrs["flags"][safe],
            arrs["ac"][safe].astype(jnp.int32),
            arrs["an"][safe].astype(jnp.int32),
            arrs["rec_id"][safe],
            gt,
            arrays_local["plane_gt2"][0][safe] & m if has_counts else None,
            arrays_local["plane_tok1"][0][safe] & m if has_counts else None,
            arrays_local["plane_tok2"][0][safe] & m if has_counts else None,
            valid,
            has_counts=has_counts,
            use_counts=use_counts,
        )
        agg["pc_call"] = mask(pr["pc_call"])
        agg["pc_tok"] = mask(pr["pc_tok"])
        agg["or_words"] = mask(pr["or_words"])
        return agg

    if sliced:
        # every local query is owned by construction (the host layout
        # routes each query — and each inert filler — to its owning
        # device's slot range); contributions scatter into the global
        # slot range, so non-owners contribute structural zeros and
        # the psum/ring combine stays a select
        out_slots = c * n_dev

        def contrib(x):
            x = x * _bcast(own_i, x)
            buf = jnp.zeros((out_slots,) + x.shape[1:], x.dtype)
            start = (me * c,) + (0,) * (x.ndim - 1)
            return jax.lax.dynamic_update_slice(buf, x, start)

    else:

        def contrib(x):
            return x * _bcast(own_i, x)

    # scalar fan-in: exactly one device owns each query, so the psum is
    # a select — the DynamoDB-counter replacement, same as sharded_query
    agg = {
        k: jax.lax.psum(contrib(res[k]), axis)
        for k in (
            "call_count",
            "n_variants",
            "all_alleles_count",
            "n_matched",
        )
    }
    agg["overflow"] = (
        jax.lax.psum(contrib(res["overflow"].astype(jnp.int32)), axis) > 0
    )
    agg["exists"] = agg["call_count"] > 0
    # record-granularity hit-row gather: block-absolute ids rebase to
    # DATASET-local (subtract the owning shard's seg_base) on device,
    # then the +1 trick turns the single-owner gather into a sum the
    # ring/all_gather combine can carry (-1 padding -> 0 contribution)
    rows = res["rows"]
    rows = jnp.where(
        rows >= 0, rows - seg_base[q["shard"]][:, None], jnp.int32(-1)
    )
    row_contrib = contrib(rows + 1)
    if masks is None:
        agg["rows"] = (
            gather_partials(row_contrib, axis, n_dev, impl=gather_impl)
            - 1
        )
        return agg

    # genotype-plane path: reduce this device's matched rows under each
    # query's own mask, then ride the SAME gather as the rows — one
    # combined ring/all_gather pass carries rows+pc_call+pc_tok+or_words
    rows_abs = res["rows"]
    valid = rows_abs >= 0
    n = arrs["pos"].shape[0]
    safe = jnp.clip(rows_abs, 0, n - 1)
    m = masks[:, None, :]  # [C, 1, W]
    gt = arrays_local["plane_gt"][0][safe] & m  # [C, R, W]
    pr = _plane_reduce(
        arrs["flags"][safe],
        arrs["ac"][safe].astype(jnp.int32),
        arrs["an"][safe].astype(jnp.int32),
        arrs["rec_id"][safe],
        gt,
        arrays_local["plane_gt2"][0][safe] & m if has_counts else None,
        arrays_local["plane_tok1"][0][safe] & m if has_counts else None,
        arrays_local["plane_tok2"][0][safe] & m if has_counts else None,
        valid,
        has_counts=has_counts,
        use_counts=use_counts,
    )
    g_rows, g_pc, g_tok, g_or = gather_partials_many(
        (
            row_contrib,
            contrib(pr["pc_call"]),
            contrib(pr["pc_tok"]),
            contrib(pr["or_words"]),
        ),
        axis,
        n_dev,
        impl=gather_impl,
    )
    agg["rows"] = g_rows - 1
    agg["pc_call"] = g_pc
    agg["pc_tok"] = g_tok
    agg["or_words"] = g_or
    return agg


def _bcast(mask_1d, x):
    """Reshape a [B] mask for broadcasting against [B, ...] ``x``."""
    return mask_1d.reshape((-1,) + (1,) * (x.ndim - 1))


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
