"""The TPU variant-query kernel.

This replaces the reference's entire splitQuery -> performQuery fan-out
(reference: lambda/splitQuery/lambda_function.py 10kb-window cross-product,
lambda/performQuery/search_variants.py per-region bcftools scan) with ONE
compiled program: a batch of queries is answered by a vmap'd fixed-depth
binary search over the sorted columnar index followed by a fixed-width
windowed gather and fully vectorised predicate evaluation.

Design notes (TPU/XLA):
- All shapes are static: the candidate window per query is ``window_cap``
  rows starting at the searchsorted lower bound; a query whose hit range
  exceeds the window reports ``overflow`` and the host falls back to the
  CPU oracle for that query (two-phase execution keeps the common case
  compiled).
- The window is contiguous, so it is read as the whole 128-lane rows
  it lies in (``lane_rows_for``), never word by word: the chip gathers
  lane rows some hundreds of times faster than single words.
- The binary search is a fixed-iteration bisection (no data-dependent
  control flow), vmapped over the query batch.
- int32 everywhere (TPU-native); no int64, no x64 mode. Chromosome
  segmentation is a 27-entry offsets table indexed by chromosome code, so
  the search key is plain ``pos``.
- "AN once per matching record" (reference :244-250) is computed with a
  windowed segmented first-match scan over ``rec_id`` — a cumsum and a
  running maximum over record starts, no scatter, no search.
"""

from __future__ import annotations

import math
import os
import threading
import warnings
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..index.columnar import (
    FLAG,
    INT32_MAX,
    VariantIndexShard,
    fnv1a32,
    pack_prefix16,
    prefix_mask,
)
from ..telemetry import note_device_stage, record_device_launch
from ..utils.chrom import chromosome_code
from ..utils.trace import graft_launch_span, span, stage

# variant_type codes for the type-dispatch mode
VT_DEL, VT_INS, VT_DUP, VT_DUP_TANDEM, VT_CNV, VT_OTHER = range(6)
_VT_CODES = {
    "DEL": VT_DEL,
    "INS": VT_INS,
    "DUP": VT_DUP,
    "DUP:TANDEM": VT_DUP_TANDEM,
    "CNV": VT_CNV,
}

# alt matching modes
MODE_EXACT, MODE_ANY_BASE, MODE_TYPE = range(3)

def __getattr__(name: str):
    """Module back-compat properties (PEP 562): ``N_LAUNCHES`` — one
    per jitted query-batch dispatch, the perf_smoke evidence that
    fused dispatch and the response cache actually collapse launches —
    now reads the device flight recorder (telemetry.py). The old
    module-global ``N_LAUNCHES += 1`` was an unlocked read-modify-write
    racing across request threads on real accelerators; the recorder's
    lock owns the increment, and the name stays readable here.
    ``tools/check_launch_recording.py`` rejects any reintroduced
    direct counter assignment."""
    if name == "N_LAUNCHES":
        from ..telemetry import flight_recorder

        return flight_recorder.kernel_launches
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )


@dataclass
class QuerySpec:
    """One Beacon variant query, coordinates 1-based inclusive."""

    chrom: str
    start_min: int
    start_max: int
    end_min: int
    end_max: int
    reference_bases: str | None = None
    alternate_bases: str | None = None
    variant_type: str | None = None
    variant_min_length: int = 0
    variant_max_length: int = -1


def encode_queries(
    queries: list[QuerySpec], shard_ids: list[int] | None = None
) -> dict[str, np.ndarray]:
    """Host-side encoding of a query batch into device arrays.

    ``shard_ids`` targets each query at one shard segment of a
    :class:`FusedDeviceIndex` (the ``shard`` field selects the row of
    its 2D ``chrom_offsets``); omitted for single-shard indexes."""
    b = len(queries)
    enc = {
        "chrom": np.zeros(b, np.int32),
        "start_min": np.zeros(b, np.int32),
        "start_max": np.zeros(b, np.int32),
        "end_min": np.zeros(b, np.int32),
        "end_max": np.zeros(b, np.int32),
        "ref_wild": np.zeros(b, np.bool_),
        "ref_hash": np.zeros(b, np.int32),
        "ref_len": np.zeros(b, np.int32),
        "alt_mode": np.zeros(b, np.int32),
        "alt_hash": np.zeros(b, np.int32),
        "alt_len": np.zeros(b, np.int32),
        "vt_code": np.zeros(b, np.int32),
        "vprefix": np.zeros((b, 4), np.uint32),
        "vprefix_mask": np.zeros((b, 4), np.uint32),
        "min_len": np.zeros(b, np.int32),
        "max_len": np.zeros(b, np.int32),
    }
    if shard_ids is not None:
        enc["shard"] = np.asarray(shard_ids, dtype=np.int32)
    for i, q in enumerate(queries):
        enc["chrom"][i] = chromosome_code(q.chrom)
        enc["start_min"][i] = q.start_min
        enc["start_max"][i] = q.start_max
        enc["end_min"][i] = q.end_min
        enc["end_max"][i] = q.end_max
        wild = q.reference_bases is None or q.reference_bases == "N"
        enc["ref_wild"][i] = wild
        if not wild:
            enc["ref_hash"][i] = fnv1a32(q.reference_bases.encode())
            enc["ref_len"][i] = len(q.reference_bases)
        if q.alternate_bases is None:
            enc["alt_mode"][i] = MODE_TYPE
            vt = q.variant_type
            enc["vt_code"][i] = _VT_CODES.get(vt, VT_OTHER)
            # '<' + str(vt): variant_type=None yields '<None', which matches
            # no alt — the reference's exact formatting artifact
            # (performQuery/search_variants.py:54)
            vpref = ("<" + str(vt)).encode()
            enc["vprefix"][i] = pack_prefix16(vpref)
            enc["vprefix_mask"][i] = prefix_mask(min(len(vpref), 16))
        elif q.alternate_bases == "N":
            enc["alt_mode"][i] = MODE_ANY_BASE
        else:
            enc["alt_mode"][i] = MODE_EXACT
            enc["alt_hash"][i] = fnv1a32(q.alternate_bases.encode())
            enc["alt_len"][i] = len(q.alternate_bases)
        enc["min_len"][i] = q.variant_min_length
        enc["max_len"][i] = (
            int(INT32_MAX) if q.variant_max_length < 0 else q.variant_max_length
        )
    return enc


#: the packed query row, one ``int32`` row a query in this column order:
#: the thirteen ``int32`` fields, ``ref_wild`` as 0 / 1, ``shard`` (0
#: where the caller gave no shard ids: ``_query_one`` reads it only
#: under a 2-D ``chrom_offsets``), then the four words each of
#: ``vprefix`` and ``vprefix_mask`` (``uint32`` viewed as ``int32``)
PACK_INT_FIELDS = (
    "chrom",
    "start_min",
    "start_max",
    "end_min",
    "end_max",
    "ref_hash",
    "ref_len",
    "alt_mode",
    "alt_hash",
    "alt_len",
    "vt_code",
    "min_len",
    "max_len",
)
_COL_REF_WILD = len(PACK_INT_FIELDS)
_COL_SHARD = _COL_REF_WILD + 1
_COL_VPREFIX = _COL_SHARD + 1
_COL_VPREFIX_MASK = _COL_VPREFIX + 4
PACK_WIDTH = _COL_VPREFIX_MASK + 4


def pack_queries(enc: dict[str, np.ndarray]) -> np.ndarray:
    """An ``encode_queries`` dictionary as ONE ``int32[b, PACK_WIDTH]``
    array (host, numpy): what ``run_queries`` and the mesh's
    ``sharded_query`` upload, once a launch. The scatter family packs
    its own (``ops/query_pack.pack_q8``)."""
    b = int(enc["chrom"].shape[0])
    packed = np.zeros((b, PACK_WIDTH), np.int32)
    for col, name in enumerate(PACK_INT_FIELDS):
        packed[:, col] = enc[name]
    packed[:, _COL_REF_WILD] = enc["ref_wild"]
    if "shard" in enc:
        packed[:, _COL_SHARD] = enc["shard"]
    packed[:, _COL_VPREFIX:_COL_VPREFIX_MASK] = enc["vprefix"].view(np.int32)
    packed[:, _COL_VPREFIX_MASK:] = enc["vprefix_mask"].view(np.int32)
    return packed


def unpack_queries(packed) -> dict:
    """The ``q`` dictionary ``_query_one`` takes, batch-leading, from a
    packed array inside a program: static column slices, the prefix
    words bit-cast back to ``uint32``, ``ref_wild`` as ``!= 0``."""
    q = {name: packed[:, col] for col, name in enumerate(PACK_INT_FIELDS)}
    q["ref_wild"] = packed[:, _COL_REF_WILD] != 0
    q["shard"] = packed[:, _COL_SHARD]
    q["vprefix"] = jax.lax.bitcast_convert_type(
        packed[:, _COL_VPREFIX:_COL_VPREFIX_MASK], jnp.uint32
    )
    q["vprefix_mask"] = jax.lax.bitcast_convert_type(
        packed[:, _COL_VPREFIX_MASK:], jnp.uint32
    )
    return q


# per-column padding fill values (pos/rec_end/rec_id = INT32_MAX so no
# searchsorted window ever selects a padding row)
_PAD_FILLS = {
    "pos": INT32_MAX,
    "rec_end": INT32_MAX,
    "ref_len": 0,
    "alt_len": 0,
    "ref_hash": 0,
    "alt_hash": 0,
    "ref_repeat_k": -1,
    "flags": 0,
    "ac": 0,
    "an": 0,
    "rec_id": INT32_MAX,
    "alt_prefix": 0,
}


#: lanes of a vector row: the unit the fused program reads a window in
LANES = 128


def lane_rows_for(window_cap: int) -> int:
    """Lane rows a window of ``window_cap`` words can lie in, wherever
    in a row it starts (``window_cap // 128 + 1`` for a multiple)."""
    return (window_cap + 2 * LANES - 2) // LANES


def pad_columns(
    cols: dict[str, np.ndarray], n: int, n_pad: int
) -> dict[str, np.ndarray]:
    """``_PAD_FILLS``-padded copies of a device-column dict (single
    shard or stacked) — THE one pad-and-fill implementation, so the
    per-shard and fused indexes can never drift on pad-row sentinels."""
    if n > n_pad:
        raise ValueError(f"{n} rows > pad target {n_pad}")
    if n_pad % LANES:
        raise ValueError(f"pad target {n_pad} is not whole {LANES}-lane rows")
    out = {}
    for name, fill in _PAD_FILLS.items():
        col = cols[name]
        padded = np.full((n_pad,) + col.shape[1:], fill, dtype=col.dtype)
        padded[:n] = col
        out[name] = padded
    return out


def pad_shard_columns(
    shard: VariantIndexShard, n_pad: int
) -> dict[str, np.ndarray]:
    """Host-side padded column dict (incl. chrom_offsets), numpy only."""
    out = pad_columns(shard.cols, shard.n_rows, n_pad)
    out["chrom_offsets"] = shard.chrom_offsets.astype(np.int32)
    return out


def padded_rows(n: int, pad_unit: int) -> int:
    return max(pad_unit, ((n + pad_unit - 1) // pad_unit) * pad_unit)


def window_hint_for(chrom_offsets, floor: int = 256) -> int:
    """Power-of-two window bound from a chromosome segment table.

    A query's candidate range is always contained in ONE (shard,
    chromosome) segment — the bisection never leaves ``[seg_lo,
    seg_hi)`` — so the widest segment bounds every ``hi - lo`` the
    kernel can produce. Launching with this instead of the engine-wide
    ``window_cap`` shrinks the window's lane rows (the launch's compute)
    without ever adding an overflow. Power-of-two with a floor, so the
    hint (a static program dimension) is stable across rebuilds."""
    offs = np.asarray(chrom_offsets)
    widest = (
        int(np.diff(offs, axis=-1).max(initial=0)) if offs.size else 0
    )
    hint = floor
    while hint < widest:
        hint *= 2
    return hint


def bisect_iters(n_pad: int) -> int:
    """Fixed bisection depth covering a padded row count."""
    return max(1, math.ceil(math.log2(n_pad + 1)))


class DeviceIndex:
    """A VariantIndexShard's device-bound columns, padded to a static shape.

    Padding rows carry pos=INT32_MAX so no searchsorted window ever selects
    them; ``chrom_offsets`` keeps real row extents.
    """

    PAD_UNIT = 8192

    def __init__(self, shard: VariantIndexShard, pad_unit: int | None = None):
        pad_unit = pad_unit or self.PAD_UNIT
        n = shard.n_rows
        n_pad = padded_rows(n, pad_unit)
        self.n_rows = n
        self.n_padded = n_pad
        self.shard = shard
        self.arrays = {
            k: jnp.asarray(v)
            for k, v in pad_shard_columns(shard, n_pad).items()
        }
        self.n_iters = bisect_iters(n_pad)
        #: measured widest-hit-range bound (see window_hint_for):
        #: run_queries clamps its window_cap to this
        self.window_hint = window_hint_for(shard.chrom_offsets)


class FusedDeviceIndex:
    """ALL warm shards stacked into one device index for fused dispatch.

    Shard rows stay contiguous and in their original order
    (``index.columnar.stack_shard_columns``); ``chrom_offsets`` becomes
    a ``[k, 27]`` per-shard segment table and each encoded query carries
    a ``shard`` id selecting its row. One ``_query_batch`` launch then
    answers (shard, query) pairs against any mix of shards — a
    k-dataset query costs ONE device launch instead of k, and the
    serving micro-batcher coalesces queries for *different* datasets
    into the same launch (previously each dataset's accumulator
    launched separately).

    The columns are resident 1-D (``alt_prefix`` ``[n, 4]``), as
    ``pad_columns`` makes them, ``n_padded`` a multiple of 8192: the
    program sees each as ``[n / 128, 128]`` (a bitcast, no copy: a
    1024-word tile of the 1-D layout is an ``(8, 128)`` tile) and reads
    a query's window as the 17 lane rows it lies in — see
    ``_query_one``.

    Row ids come back as absolute stacked ids; ``shard_base[sid]``
    maps them back to shard-local ids for host materialisation. The
    index holds its own column copy (the per-shard device indexes —
    XLA gather or scatter-tile — stay alive for fallback and
    single-target paths), so the engine only builds it when >= 2
    shards are warm and the stacked row count fits
    ``fused_max_rows`` — budget notes in DEPLOYMENT.md.
    """

    PAD_UNIT = 8192

    #: flight-recorder program family (the L0 subclass overrides —
    #: tools/check_launch_recording.py pins the override literal)
    flight_family = "fused"

    def __init__(
        self, shards: list[VariantIndexShard], pad_unit: int | None = None
    ):
        from ..index.columnar import stack_shard_columns

        cols, chrom_offsets, base = stack_shard_columns(shards)
        n = int(base[-1])
        n_pad = padded_rows(n, pad_unit or self.PAD_UNIT)
        arrays = {
            k: jnp.asarray(v)
            for k, v in pad_columns(cols, n, n_pad).items()
        }
        arrays["chrom_offsets"] = jnp.asarray(chrom_offsets)
        self.arrays = arrays
        self.n_rows = n
        self.n_padded = n_pad
        self.n_iters = bisect_iters(n_pad)
        self.n_shards = len(shards)
        #: shard count as compiled (the L0 subclass pads the segment
        #: table, so its program identity uses the padded count)
        self.n_shards_padded = len(shards)
        self.shard_base = base  # int64[k+1]
        #: ragged-window bound generalised from the L0 mini-index
        #: (ISSUE 17): the widest (shard, chromosome) segment of the
        #: stack bounds every candidate range, so record-heavy
        #: launches stop paying the engine-wide window_cap gather
        #: width (the L0 subclass overrides with its tail-shard bound)
        self.window_hint = window_hint_for(chrom_offsets)

    def to_local_rows(self, rows: np.ndarray, sid: int) -> np.ndarray:
        """Stacked row ids (already -1-filtered) -> shard-local ids."""
        return rows.astype(np.int64) - int(self.shard_base[sid])


class L0DeviceIndex(FusedDeviceIndex):
    """The delta-tail mini-index — the LSM ``memtable -> L0`` tier
    (ISSUE 15), stacked over a key's standing delta shards.

    Same layout as :class:`FusedDeviceIndex` (``stack_shard_columns``
    over the tail shards — small rows, contiguous per-shard spans, a
    per-shard segment table row selected by the encoded query's
    ``shard`` id), with one addition: the ``[k, 27]`` segment table is
    padded up to a fixed shard-count tier (all-zero rows — every
    segment empty, so a pad shard can never match). The tail grows by
    one shard per delta publish, and without the pad each rebuild
    would be a novel ``[k, 27]`` operand shape — a fresh XLA compile
    per publish, exactly the mid-request-compile tail the batch tiers
    exist to prevent. With it, successive tail builds inside one tier
    reuse ONE compiled program, and the engine pre-warms the batch
    tiers at build time (off the request path).

    Launches against this index report to the flight recorder as the
    ``fused_l0`` family, so /device/status and ``device.launches``
    attribute tail serving separately from the base fused stack."""

    flight_family = "fused_l0"

    #: pad-to tiers for the segment table's shard axis
    SHARD_TIERS = (8, 16, 32, 64, 128, 256, 512)

    def __init__(
        self, shards: list[VariantIndexShard], pad_unit: int | None = None
    ):
        super().__init__(shards, pad_unit=pad_unit)
        k = self.n_shards
        k_pad = next((t for t in self.SHARD_TIERS if k <= t), k)
        co = np.asarray(self.arrays["chrom_offsets"])
        if k_pad != k:
            pad = np.zeros((k_pad - k, co.shape[1]), dtype=co.dtype)
            co = np.concatenate([co, pad])
            self.arrays["chrom_offsets"] = jnp.asarray(co)
        #: host copy of the padded segment table: the per-key composite
        #: (CompositeL0DeviceIndex) shifts and restacks it without a
        #: device round-trip per rebuild
        self.chrom_offsets_host = co
        self.n_shards_padded = k_pad
        # a tail shard's candidate window can never exceed its own
        # row count, so the launch may run with a window sized to the
        # LARGEST tail shard instead of the engine-wide window_cap —
        # the window's lane rows (the launch's compute) shrink ~8-16x
        # for typical tails. Power-of-two with a floor, so the hint
        # (a static program dimension) is stable across builds.
        widest = max((s.n_rows for s in shards), default=1)
        hint = 256
        while hint < widest:
            hint *= 2
        self.window_hint = hint

    #: finer batch-tier ladder than the global BATCH_TIERS: a deep-tail
    #: query submits one spec per covered tail shard (typically 9-32),
    #: and padding those to the global 64 tier quadruples the launch's
    #: compute. The L0 program is tiny (window_hint-sized gathers over
    #: <=8192 rows), so the extra compiled tiers cost little and the
    #: engine pre-warms them at build time.
    batch_tiers = (8, 16, 32, 64, 512, 2048)


class CompositeL0DeviceIndex:
    """Per-key L0 blocks assembled into ONE serving index (ISSUE 20).

    The per-(dataset, vcf) L0 refactor keeps a standing
    :class:`L0DeviceIndex` block per covered key, so a delta publish to
    key A re-stacks (host gather + device upload) ONLY key A's block.
    Serving still holds the single-launch contract — ``l0_pre_rows``
    answers every covered tail row across keys with ONE coalesced
    launch — and this class is what squares the two: the blocks'
    device-resident row columns concatenate device-side (HBM-to-HBM, no
    host restack of untouched keys), each block's padded ``[k, 27]``
    segment table shifts by the block's row offset and stacks along the
    shard axis (a pad shard's all-zero row shifts to ``[off, off)`` —
    still empty, still unmatchable), and composite shard ids index the
    stacked table. It exposes the same attribute surface ``run_queries``
    reads (``arrays`` / ``n_iters`` / ``n_shards_padded`` /
    ``window_hint`` / ``flight_family`` / ``batch_tiers`` /
    ``to_local_rows``), so the launch path cannot tell it from a
    monolithic stack; the class name rides the program identity, so its
    programs never alias the monolithic index's."""

    flight_family = "fused_l0"
    batch_tiers = L0DeviceIndex.batch_tiers

    def __init__(self, blocks: list[L0DeviceIndex]):
        if not blocks:
            raise ValueError("CompositeL0DeviceIndex needs >= 1 block")
        parts: dict[str, list] = {}
        co_parts: list[np.ndarray] = []
        base_parts: list[np.ndarray] = []
        #: composite sid of each block's shard 0 (block order preserved)
        self.block_sid_offsets: list[int] = []
        row_off = 0
        sid_off = 0
        for b in blocks:
            self.block_sid_offsets.append(sid_off)
            co = b.chrom_offsets_host
            co_parts.append((co + row_off).astype(co.dtype, copy=False))
            sb = np.asarray(b.shard_base, dtype=np.int64)
            # pad shards (sid past the block's real count) clamp to the
            # block's end base: they are never routed, but the base
            # array must stay index-aligned with the stacked table
            clamp = np.minimum(np.arange(b.n_shards_padded), b.n_shards)
            base_parts.append(sb[clamp] + row_off)
            for name, arr in b.arrays.items():
                if name != "chrom_offsets":
                    parts.setdefault(name, []).append(arr)
            row_off += b.n_padded
            sid_off += b.n_shards_padded
        self.arrays = {
            name: (vals[0] if len(vals) == 1 else jnp.concatenate(vals))
            for name, vals in parts.items()
        }
        self.arrays["chrom_offsets"] = jnp.asarray(np.concatenate(co_parts))
        self.blocks = list(blocks)
        self.n_rows = sum(b.n_rows for b in blocks)
        self.n_padded = row_off
        self.n_iters = bisect_iters(row_off)
        self.n_shards = sum(b.n_shards for b in blocks)
        self.n_shards_padded = sid_off
        self.shard_base = np.concatenate(
            base_parts + [np.asarray([row_off], dtype=np.int64)]
        )
        self.window_hint = max(b.window_hint for b in blocks)

    def to_local_rows(self, rows: np.ndarray, sid: int) -> np.ndarray:
        """Stacked row ids (already -1-filtered) -> shard-local ids."""
        return rows.astype(np.int64) - int(self.shard_base[sid])


@dataclass
class QueryResults:
    """Per-query aggregates + matched row ids (numpy, host-side)."""

    exists: np.ndarray  # bool[B]
    call_count: np.ndarray  # int32[B] — sum of AC over matched rows
    n_variants: np.ndarray  # int32[B] — matched rows with AC != 0
    all_alleles_count: np.ndarray  # int32[B] — AN summed once per record
    n_matched: np.ndarray  # int32[B]
    overflow: np.ndarray  # bool[B] — window_cap exceeded, host fallback
    rows: np.ndarray  # int32[B, record_cap] global row ids, -1 padded


def _bisect(pos, target, lo0, hi0, n_iters, *, upper: bool):
    """Fixed-depth bisection over pos[lo0:hi0].

    upper=False: first index with pos[idx] >= target (lower bound).
    upper=True:  first index with pos[idx] >  target (upper bound) — used
    instead of lower_bound(target+1) so target=INT32_MAX cannot wrap.
    """

    def body(carry, _):
        lo, hi = carry
        # once lo == hi the search is done; further probes would read
        # pos[mid] outside [lo0, hi0) and walk past the segment end
        active = lo < hi
        mid = (lo + hi) // 2
        less = pos[mid] <= target if upper else pos[mid] < target
        return (
            jnp.where(active & less, mid + 1, lo),
            jnp.where(active & ~less, mid, hi),
        ), None

    (lo, _), _ = jax.lax.scan(body, (lo0, hi0), None, length=n_iters)
    return lo


def _query_one(arrays, q, *, window_cap: int, record_cap: int, n_iters: int):
    """One query against one index (vmapped over the batch): THE
    predicate of every XLA index class and of the mesh programs.

    Two bisections give the candidate range ``[lo, hi)``; the window
    ``[lo, lo + window_cap)`` is then read in whole 128-lane rows, each
    column seen as ``[n / 128, 128]``: ``lane_rows_for(window_cap)``
    rows from row ``lo // 128``, lanes outside the window invalid. On
    the chip a gather of single words from a 6.4e7-row column took
    1.7 ms a column a launch, twelve columns a launch; the same window
    as 17 lane rows is one of the row gathers the plane programs
    already make (PERF.md 6, PR 32). The answers are those of a
    word-by-word read, bit for bit."""
    pos = arrays["pos"]
    offsets = arrays["chrom_offsets"]
    n = pos.shape[0]

    if offsets.ndim == 2:
        # fused multi-shard index: the query's shard id selects its
        # segment table row; the bisection then never leaves that
        # shard's contiguous row span
        seg_lo = offsets[q["shard"], q["chrom"]]
        seg_hi = offsets[q["shard"], q["chrom"] + 1]
    else:
        seg_lo = offsets[q["chrom"]]
        seg_hi = offsets[q["chrom"] + 1]
    lo = _bisect(pos, q["start_min"], seg_lo, seg_hi, n_iters, upper=False)
    hi = _bisect(pos, q["start_max"], seg_lo, seg_hi, n_iters, upper=True)

    # rows past the column's end repeat the last one: their lanes, like
    # those before lo, at or past hi, or past the window, are invalid
    rows_at = lo // LANES + jnp.arange(
        lane_rows_for(window_cap), dtype=jnp.int32
    )
    idxs = (
        rows_at[:, None] * LANES + jnp.arange(LANES, dtype=jnp.int32)
    ).reshape(-1)
    valid = (idxs >= lo) & (idxs < hi) & (idxs - lo < window_cap)
    safe_rows = jnp.clip(rows_at, 0, n // LANES - 1)

    def g(name):
        col = arrays[name]  # [n] or, alt_prefix, [n, 4]
        tail = col.shape[1:]
        return col.reshape((-1, LANES) + tail)[safe_rows].reshape((-1,) + tail)

    rec_end = g("rec_end")
    end_ok = (q["end_min"] <= rec_end) & (rec_end <= q["end_max"])

    ref_ok = q["ref_wild"] | (
        (g("ref_hash") == q["ref_hash"]) & (g("ref_len") == q["ref_len"])
    )

    alt_len = g("alt_len")
    len_ok = (q["min_len"] <= alt_len) & (alt_len <= q["max_len"])

    flags = g("flags")
    f = lambda bit: (flags & bit) != 0
    sym = f(FLAG.SYMBOLIC)
    k = g("ref_repeat_k")
    ref_len = g("ref_len")

    # symbolic-prefix match: first L bytes of alt equal '<'+variant_type
    ap = g("alt_prefix")  # [lanes, 4] uint32
    pm = jnp.all(
        ((ap ^ q["vprefix"][None, :]) & q["vprefix_mask"][None, :]) == 0, axis=1
    )

    del_ok = jnp.where(sym, pm | f(FLAG.CN0), alt_len < ref_len)
    ins_ok = jnp.where(sym, pm, alt_len > ref_len)
    dup_ok = jnp.where(
        sym, pm | (f(FLAG.CN_PREFIX) & ~f(FLAG.CN0) & ~f(FLAG.CN1)), k >= 2
    )
    dupt_ok = jnp.where(sym, pm | f(FLAG.CN2), k == 2)
    cnv_ok = jnp.where(
        sym,
        pm | f(FLAG.CN_PREFIX) | f(FLAG.DEL_PREFIX) | f(FLAG.DUP_PREFIX),
        f(FLAG.DOT) | (k >= 1),
    )
    other_ok = sym & pm
    type_ok = jnp.select(
        [
            q["vt_code"] == VT_DEL,
            q["vt_code"] == VT_INS,
            q["vt_code"] == VT_DUP,
            q["vt_code"] == VT_DUP_TANDEM,
            q["vt_code"] == VT_CNV,
        ],
        [del_ok, ins_ok, dup_ok, dupt_ok, cnv_ok],
        other_ok,
    )
    exact_ok = (g("alt_hash") == q["alt_hash"]) & (alt_len == q["alt_len"])
    anyb_ok = f(FLAG.SINGLE_BASE)
    alt_ok = jnp.where(
        q["alt_mode"] == MODE_EXACT,
        exact_ok,
        jnp.where(q["alt_mode"] == MODE_ANY_BASE, anyb_ok, type_ok),
    )

    matched = valid & end_ok & ref_ok & len_ok & alt_ok

    ac = g("ac")
    call_count = jnp.sum(jnp.where(matched, ac, 0))
    n_variants = jnp.sum(matched & (ac != 0))
    n_matched = jnp.sum(matched)

    # AN once per record with >= 1 matched row: segmented first-match scan
    rec_w = jnp.where(valid, g("rec_id"), INT32_MAX)
    m_i = matched.astype(jnp.int32)
    before_all = jnp.cumsum(m_i) - m_i  # matched strictly before lane i
    # ... and strictly before the lane its record starts at: before_all
    # never falls, so its running maximum over record starts is its
    # value at the nearest one (a record's rows are adjacent)
    starts = jnp.concatenate([jnp.ones(1, bool), rec_w[1:] != rec_w[:-1]])
    before_seg = jax.lax.cummax(jnp.where(starts, before_all, 0))
    first_match = matched & (before_all == before_seg)
    all_alleles = jnp.sum(jnp.where(first_match, g("an"), 0))

    # matched row ids, ascending, -1 padded, capped at record_cap
    marked = jnp.where(matched, idxs, INT32_MAX)
    topk = jax.lax.sort(marked)[: min(record_cap, window_cap)]
    rows = jnp.where(topk == INT32_MAX, -1, topk)

    return {
        "exists": call_count > 0,
        "call_count": call_count,
        "n_variants": n_variants,
        "all_alleles_count": all_alleles,
        "n_matched": n_matched,
        "overflow": (hi - lo) > window_cap,
        "rows": rows,
    }


def _query_batch_impl(arrays, packed, *, window_cap, record_cap, n_iters):
    fn = partial(
        _query_one,
        arrays,
        window_cap=window_cap,
        record_cap=record_cap,
        n_iters=n_iters,
    )
    return jax.vmap(fn)(unpack_queries(packed))


_JIT_STATICS = ("window_cap", "record_cap", "n_iters")

#: the jitted query-batch entry (tools/check_launch_recording.py pins
#: run_queries as its one caller)
_query_batch = partial(jax.jit, static_argnames=_JIT_STATICS)(
    _query_batch_impl
)

#: same program, but the packed query batch (positional arg 1) is
#: DONATED: steady-state serving uploads a fresh one per launch, and
#: without donation XLA double-buffers it in HBM next to its output.
#: The index arrays (arg 0) are persistent and never donated. A buffer
#: whose shape/dtype match no output is simply freed rather than
#: aliased — that is still the win — so the advisory "donated buffers
#: were not usable" warning is noise here.
_query_batch_donated = partial(
    jax.jit, static_argnames=_JIT_STATICS, donate_argnums=(1,)
)(_query_batch_impl)


@contextmanager
def _quiet_donation():
    """Silence the advisory unusable-donation warning around a donated
    launch — a module-level filter would be undone by test harnesses
    that reset warning state per test."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


def _donate_uploads() -> bool:
    """Process default for encode-buffer donation on the upload path
    (``BEACON_DONATE_UPLOADS``; on unless explicitly disabled)."""
    return os.environ.get(
        "BEACON_DONATE_UPLOADS", "1"
    ).lower() not in ("0", "false", "off", "no")


# the LEGACY fixed batch-size tiers (<=8x padding overhead, 4 programs
# total); batches beyond the top tier run at their exact size (bulk
# benchmark shapes, not serving). Kept as the documented baseline and
# the BEACON_TIER_LADDER=legacy escape hatch — live tier selection
# consults the process TierLadder below (ISSUE 17).
BATCH_TIERS = (8, 64, 512, 2048)


class TierLadder:
    """The batch-size tier ladder every padding seam consults.

    PR 14's flight recorder showed the coarse ``BATCH_TIERS`` ladder
    wasting up to 7 of 8 padded lanes at tier boundaries (worst
    (family, tier) cells ~0.86), and PR 15's private finer ladder on
    the L0 mini-index proved finer rungs pay for themselves: the extra
    compiled programs are warmed off the request path and the padding
    waste collapses. This class promotes that ladder to a single
    process-wide source of truth — ``run_queries`` batch padding and
    the engine's warmup loops read the SAME instance, so a rung can
    never exist for serving without being pre-compiled
    (``tools/check_launch_recording.py`` lints the parity).

    Rungs are fit to measured traffic: :meth:`fit` reads the
    recorder's per-(family, tier) real-vs-padded histogram and splits
    any rung whose waste exceeds ``WASTE_SPLIT`` — or the operator
    pins the ladder with ``BEACON_TIER_LADDER`` (comma-separated rungs,
    or ``legacy`` for the old 4-tier ladder)."""

    #: the L0-proven default (PR 15): fills the 8->64 gap where the
    #: recorder saw the worst serving-tier waste
    DEFAULT_RUNGS = (8, 16, 32, 64, 512, 2048)
    #: a (family, tier) histogram cell wasting more than this fraction
    #: of its padded lanes earns a finer rung below it
    WASTE_SPLIT = 0.5
    #: fit() never grows the ladder beyond this many rungs (each rung
    #: is a compiled program per family — warmup time and program
    #: cache both scale with it)
    MAX_RUNGS = 12
    #: families whose recorded padding is not a batch rung's: a
    #: ``plane`` launch's tier is its launch group's slot count and its
    #: padding the members a request did not ask, which a finer batch
    #: rung cannot fix, so fit() must not chase it
    FIT_SKIP_FAMILIES = frozenset({"plane"})

    __slots__ = ("rungs", "source")

    def __init__(self, rungs, source: str = "default"):
        clean = tuple(sorted({int(r) for r in rungs if int(r) > 0}))
        if not clean:
            raise ValueError("TierLadder needs at least one rung")
        self.rungs = clean
        self.source = source

    def tier_for(self, b: int):
        """Smallest rung holding a batch of ``b``; None past the top
        rung (bulk batches run at their exact size)."""
        return next((t for t in self.rungs if b <= t), None)

    @classmethod
    def from_env(cls, env=None) -> "TierLadder":
        """The env-pinned ladder (``BEACON_TIER_LADDER``: comma rungs
        or ``legacy``), else the default. Malformed values fall back
        to the default — a bad knob must not take serving down."""
        raw = (env if env is not None else os.environ).get(
            "BEACON_TIER_LADDER", ""
        ).strip()
        if not raw:
            return cls(cls.DEFAULT_RUNGS, source="default")
        if raw.lower() == "legacy":
            return cls(BATCH_TIERS, source="env")
        try:
            return cls(
                [int(p) for p in raw.split(",") if p.strip()],
                source="env",
            )
        except ValueError:
            return cls(cls.DEFAULT_RUNGS, source="default")

    def fit(self, pad_tier_hist: dict) -> "TierLadder":
        """A traffic-fit refinement of this ladder: any (family, tier)
        cell of the recorder's real-vs-padded histogram wasting more
        than ``WASTE_SPLIT`` of its padded lanes earns the half-rung
        below its tier (repeatedly halving would chase noise; one
        split per observed-bad rung per fit keeps the ladder bounded
        and the warmup cheap). Families padded by something other
        than a rung (``FIT_SKIP_FAMILIES``) and splits below the ladder
        floor are ignored, so successive fits converge — warming the
        fitted ladder never creates cells that would re-split it. Rung
        count is capped at MAX_RUNGS, keeping the worst offenders."""
        splits = []
        for (family, tier), (real, padded) in pad_tier_hist.items():
            tier = int(tier)
            if family in self.FIT_SKIP_FAMILIES:
                continue
            half = tier // 2
            # never split below the ladder floor: waste at the bottom
            # rung is the floor's known cost, not a mis-fit ladder, and
            # sub-floor rungs would leak into every consumer of
            # active_ladder() (a 3-query batch must keep padding to 8)
            if not padded or tier not in self.rungs or half < self.rungs[0]:
                continue
            waste = 1.0 - real / padded
            if waste > self.WASTE_SPLIT and half not in self.rungs:
                splits.append((waste, half))
        if not splits:
            return self
        splits.sort(reverse=True)
        budget = max(0, self.MAX_RUNGS - len(self.rungs))
        extra = []
        for _waste, rung in splits:
            if rung in extra:
                continue
            if len(extra) >= budget:
                break
            extra.append(rung)
        if not extra:
            return self
        return TierLadder(self.rungs + tuple(extra), source="fit")


_LADDER_LOCK = threading.Lock()
_ACTIVE_LADDER: TierLadder | None = None


def active_ladder() -> TierLadder:
    """The process tier ladder — THE single source every padding seam
    (run_queries and all warmup loops) consults."""
    global _ACTIVE_LADDER
    with _LADDER_LOCK:
        if _ACTIVE_LADDER is None:
            _ACTIVE_LADDER = TierLadder.from_env()
        return _ACTIVE_LADDER


def set_active_ladder(ladder: TierLadder | None) -> None:
    """Install (or with None, reset to env/default) the process
    ladder. Callers own re-warming: a rung that reaches serving
    without a warmup compile is exactly what the warmup-ladder lint
    exists to catch."""
    global _ACTIVE_LADDER
    with _LADDER_LOCK:
        _ACTIVE_LADDER = ladder


def refit_active_ladder(recorder=None) -> TierLadder:
    """Traffic-fit the process ladder from the flight recorder's
    per-(family, tier) histogram — the engine calls this at the top of
    ``warmup()``, so every fitted rung is pre-compiled in the same
    warmup phase. An env-pinned ladder (``BEACON_TIER_LADDER``) is the
    operator's word and never refit."""
    global _ACTIVE_LADDER
    if recorder is None:
        from ..telemetry import flight_recorder as recorder
    with _LADDER_LOCK:
        ladder = _ACTIVE_LADDER or TierLadder.from_env()
        if ladder.source != "env":
            ladder = ladder.fit(recorder.pad_tier_histogram())
        _ACTIVE_LADDER = ladder
        return ladder


class PendingQueryResults:
    """An in-flight query batch: the launch has been dispatched, the
    device-to-host fetch is deferred to :meth:`fetch`.

    JAX dispatch is asynchronous — ``_query_batch`` returns device
    futures — so splitting launch from fetch lets the serving layer
    overlap host work (encoding batch i+1, materialising batch i-1)
    with the device execution of batch i instead of blocking the
    launcher thread inside ``device_get``."""

    __slots__ = ("_out", "_b", "flight_seq")

    def __init__(self, out, b: int, flight_seq: int | None = None):
        self._out = out
        self._b = b
        #: the launch's flight-recorder record: fetch attaches its
        #: device-readback wall time there (serving's launch/fetch
        #: stages run on different threads, so the seq is the handle)
        self.flight_seq = flight_seq

    def fetch(self) -> QueryResults:
        with stage("kernel.readback") as st:
            out = jax.device_get(self._out)
        with stage("kernel.unpack"):
            note_device_stage(
                self.flight_seq,
                fetch_ms=st.ms,
                fetch_bytes=sum(
                    np.asarray(v).nbytes for v in out.values()
                ),
            )
            self._out = None  # free the device buffers promptly
            b = self._b
            return QueryResults(
                exists=np.asarray(out["exists"])[:b],
                call_count=np.asarray(out["call_count"])[:b],
                n_variants=np.asarray(out["n_variants"])[:b],
                all_alleles_count=np.asarray(out["all_alleles_count"])[:b],
                n_matched=np.asarray(out["n_matched"])[:b],
                overflow=np.asarray(out["overflow"])[:b],
                rows=np.asarray(out["rows"])[:b],
            )


class ReadyQueryResults:
    """Already-fetched results behind the PendingQueryResults contract
    (kernels that execute synchronously, e.g. the scatter tile path)."""

    __slots__ = ("_res",)

    def __init__(self, res: QueryResults):
        self._res = res

    def fetch(self) -> QueryResults:
        return self._res


def padded_batch(dindex, b: int) -> int:
    """The batch size a launch of ``b`` queries on this index runs at:
    the smallest rung holding it, on the index's own ladder where it
    carries one (``batch_tiers``: the L0 mini-indexes) and on the
    process ladder otherwise; past the top rung, and for an empty
    batch, ``b`` itself. ``run_queries`` pads to it, and the
    micro-batcher fills a launch no further (``ops.launch_capacity``)."""
    if not b:
        return 0
    tiers = getattr(dindex, "batch_tiers", None)
    if tiers is None:
        tiers = active_ladder().rungs
    return next((t for t in tiers if b <= t), b)


def run_queries(
    dindex: DeviceIndex,
    queries: list[QuerySpec] | dict[str, np.ndarray],
    *,
    window_cap: int = 2048,
    record_cap: int = 1024,
    async_fetch: bool = False,
):
    """Execute a query batch against one device index (single-shard
    ``DeviceIndex`` or stacked ``FusedDeviceIndex``; fused batches must
    arrive pre-encoded with their ``shard`` ids).

    The encoded batch goes up as ONE packed ``int32`` array
    (``pack_queries``; the program unpacks it, ``unpack_queries``), one
    host-to-device transfer a launch, counted in
    ``device.query_uploads``; a caller's dictionary or list is packed
    here, at the seam.

    The batch pads up to a fixed size tier (``BATCH_TIERS``, repeating
    query 0 — always semantically inert, outputs trimmed) so the
    compiled-program cache is keyed by a handful of shapes instead of
    every micro-batch size the serving batcher can emit: un-padded, a
    16-client soak compiled a fresh program per novel batch size
    mid-request — the r4 soak tail (VERDICT r4 next #7).

    ``async_fetch=True`` returns a :class:`PendingQueryResults` right
    after dispatch (launch/fetch overlap); default blocks and returns
    :class:`QueryResults`.
    """
    with stage("kernel.encode"):
        enc = (
            encode_queries(queries) if isinstance(queries, list) else queries
        )
        b = int(enc["chrom"].shape[0])
        # ragged-window clamp: the index's measured widest-hit-range bound
        # (never adds an overflow — see window_hint_for). Applied HERE, the
        # one choke point, so warmup and serving can't compile different
        # window shapes for the same index.
        window_cap = min(
            window_cap, getattr(dindex, "window_hint", window_cap)
        )
        # an index may carry its own (finer) tier ladder — the L0
        # mini-index does, so a per-tail-shard spec batch is not padded to
        # the global 64 tier; everything else pads to the process ladder
        padded = padded_batch(dindex, b)
        packed = pack_queries(enc)
        if padded != b:
            packed = np.concatenate(
                [packed, np.repeat(packed[:1], padded - b, axis=0)]
            )
    donate = _donate_uploads()
    with span("kernel.run_queries") as sp:
        with stage("kernel.dispatch") as st:
            # ONE upload a launch: seventeen, key by key, were most of
            # this stage (PERF.md 6, PR 42)
            packed_dev = jnp.asarray(packed)
            batch_fn = _query_batch_donated if donate else _query_batch
            with _quiet_donation():
                out = batch_fn(
                    dindex.arrays,
                    packed_dev,
                    window_cap=window_cap,
                    record_cap=record_cap,
                    n_iters=dindex.n_iters,
                )
        launch_ms = st.ms
        # ONE flight-recorder seam per launch: counters, the launch
        # ring, and compile tracking (a first-seen (program, shape)
        # key below is an XLA compile — jit traces inside this call).
        # The family comes off the index (fused vs fused_l0): L0
        # tail launches are attributable separately from base-stack
        # launches on every recorder surface.
        family = getattr(dindex, "flight_family", "fused")
        seq = record_device_launch(
            family,
            seam="kernel",
            tier=padded,
            specs_real=b,
            specs_padded=padded,
            # every spec of a stacked-index batch is one (query,
            # dataset) pair
            evaluated_pairs=b,
            launch_ms=launch_ms,
            donated=1 if donate else 0,
            uploads=1,
            # the XLA-gather families are not placed: their arrays lie
            # on the default device
            chip=0,
            program_key=(
                "xla_gather",
                # the donated entry is a distinct compiled program
                # (separate jit cache), so donation is program identity
                "don" if donate else "nodon",
                type(dindex).__name__,
                dindex.n_padded,
                # a fused stack rebuild can keep n_padded while its
                # [k, 27] segment table grows a row — a distinct XLA
                # program, so the (padded) shard count is part of the
                # identity; the L0 index pads it to a tier exactly so
                # this key stays stable across tail builds
                getattr(
                    dindex,
                    "n_shards_padded",
                    getattr(dindex, "n_shards", 1),
                ),
                dindex.n_iters,
                padded,
                window_cap,
                record_cap,
            ),
        )
        sp.note(batch=b)
        graft_launch_span(
            sp,
            elapsed_ms=launch_ms,
            family=family,
            tier=padded,
            specs=b,
        )
    pending = PendingQueryResults(out, b, seq)
    if async_fetch:
        return pending
    return pending.fetch()
