"""Device-resident genotype bit planes + in-kernel masked reductions.

Round-3 left the selected-samples leaf half on host: the device matched
rows, then sample restriction ran as numpy popcounts over HOST-resident
genotype planes (~25 GB at 1000-Genomes width — engine.materialize_
response), capping the path at one host's RAM (VERDICT r3 missing #2).
This module puts the planes themselves in HBM and runs the per-row
masked popcounts and the sample-hit OR-reduction in one jitted program:

- ``PlaneDeviceIndex`` holds the shard's planes as int32 device arrays
  whose minor dimension is whole 128-lane rows, the layout a TPU row
  gather reads as it is (an ``[n, W]`` argument, W = ceil(n_samples/32)
  words, is re-tiled whole inside every program that gathers from it).
  A row of more than 64 words is zero-padded to the next multiple of
  128, as many lane rows as that takes: ``[n, Wp]``, one lane row and
  512 B/row/plane at 2504 samples, 112 lane rows and 57,344 B at the
  454,787 of a biobank (``4 x padded_words(ceil(n / 32))`` B: size a
  chip by samples x rows). A narrower row is
  zero-padded to p words, p the least power of two >= W, and k = 128 // p
  rows share one lane row: ``[ceil(n / k), 128]``, 128 B/row/plane at
  1000 samples (``pack_factor``, ``resident_shape``; n counts whole
  steps of 128 rows, so cohorts of like size share programs). The
  count planes
  (gt2/tok1/tok2) are uploaded only when the
  shard has genotype-derived rows at all — INFO-sourced corpora (the
  common cohort-VCF case, and the bench corpus) only ever touch ``gt``
  for sample-hit extraction, so only it occupies HBM.
- ``plane_row_stats`` gathers the matched rows' plane words eight rows
  a step (``reduce_rows``: a launch's workspace is one block of rows at
  any width, and it reads the real rows alone), ANDs the
  selected-sample mask, and returns per-row popcounts ``[R, 4]`` plus
  the OR of ``gt & mask`` over a caller-chosen row subset — the exact
  quantities ``materialize_response`` popcounted on host. The reference
  semantics (cumulative-truncation k0, ploidy>2 overflow side tables)
  stay host-side and UNCHANGED: the device call replaces only the
  bandwidth-heavy plane reads.

Capacity: a plane set that does not fit the configured HBM budget stays
host-resident and the engine serves exactly as before (the fallback is
the round-3 path, not an error). Multi-chip: planes shard row-wise with
their dataset over the mesh — ``parallel/mesh.py`` stacks them like the
index columns and the dryrun proves the sharded layout.

Reference parity: per-sample hit extraction and genotype-derived
counting mirror performQuery/search_variants_in_samples.py (the
reference's ``--samples`` bcftools leaf, search_variants.py:233-258).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..index.columnar import FLAG, VariantIndexShard
from ..telemetry import note_device_stage, record_device_launch

# R padding tiers: one compiled program per (tier, flags) combination;
# larger row sets chunk through the top tier (bounded compile cache)
_R_TIERS = (128, 1024, 8192)


#: words of one resident lane row: the minor tile of a TPU array
LANES = 128


def padded_words(n_words: int) -> int:
    """Words a row of ``n_words`` takes in the resident array: rounded
    up to whole 128-lane rows above 64 words, else to the least power
    of two that holds it (so that ``pack_factor`` rows fill a lane
    row)."""
    if n_words > LANES // 2:
        return -(-n_words // LANES) * LANES
    return 1 << max(0, n_words - 1).bit_length()


def pack_factor(n_words: int) -> int:
    """k: rows of a plane that share one resident lane row (4 at 32
    words, 2 up to 64, 1 above). It follows from the width alone, so
    the upload, the budget and the programs agree without being told."""
    return max(1, LANES // padded_words(n_words))


#: rows a resident plane grows by: the rows of one tile of the match
#: index (``ScatterDeviceIndex.tile``), whose tile count a row-gathering
#: program is compiled for beside the plane's shape
ROW_STEP = 128


def resident_shape(n_rows: int, n_words: int) -> tuple[int, int]:
    """Shape of the resident array of an ``[n_rows, n_words]`` plane:
    ``[ceil(n / k), k * padded_words]``, n = ``n_rows`` rounded up to
    whole ``ROW_STEP`` rows; row r lies in lane row ``r // k`` at words
    ``(r % k) * padded_words`` onward (the rows past ``n_rows`` are
    zeros nobody reads). A program is compiled for its operands'
    shapes, and a launch group's for every member's: in whole steps,
    cohorts of like size (the same cohort a few rows later, a
    benchmark's next seed) share their programs instead of compiling
    their own, at under 128 rows of padding a plane."""
    k = pack_factor(n_words)
    n = -(-n_rows // ROW_STEP) * ROW_STEP
    return -(-n // k), k * padded_words(n_words)


def masked_rows(plane, rows, mask):
    """``plane[rows] & mask`` read from the resident layout: int32
    ``[..., R, lanes]`` for ``rows`` ``[..., R]`` (below
    ``plane.shape[0] * k``) and ``mask`` ``[..., W]``. With k rows to a
    lane row the whole lane row ``rows // k`` is gathered and the mask,
    zero-extended and repeated for every part, keeps part ``rows % k``
    alone: the other parts and the padding words come out zero, so a
    popcount over all lanes counts row r and nothing else."""
    n_words = mask.shape[-1]
    k = pack_factor(n_words)
    p = plane.shape[1] // k
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, p - n_words)])
    m = m[..., None, :]
    if k == 1:
        return plane[rows] & m
    part = jax.lax.iota(jnp.int32, plane.shape[1]) // p
    keep = part == (rows % k)[..., None]
    return plane[rows // k] & jnp.where(keep, jnp.tile(m, k), jnp.int32(0))


def fold_parts(words, n_words: int):
    """``[..., lanes]`` words OR-reduced over ``masked_rows`` outputs ->
    the ``[..., n_words]`` OR of the rows themselves: the k parts of
    the lane row folded together, the padding words cut."""
    k = pack_factor(n_words)
    if k > 1:
        words = jax.lax.reduce(
            words.reshape(words.shape[:-1] + (k, words.shape[-1] // k)),
            np.int32(0),
            jax.lax.bitwise_or,
            dimensions=(words.ndim - 1,),
        )
    return words[..., :n_words]


#: rows one step of ``reduce_rows`` gathers: the sublanes of one tile
ROW_BLOCK = 8


def row_blocks(n_rows):
    """Steps ``reduce_rows`` takes for ``n_rows`` rows at the front of
    a slot (numpy or jnp, scalar or array)."""
    return (n_rows + ROW_BLOCK - 1) // ROW_BLOCK


def gathered_bytes(plane, n_rows, n_planes: int) -> int:
    """Bytes ``reduce_rows`` reads from ``n_planes`` resident planes
    shaped as ``plane`` for slots of ``n_rows`` real rows (an int or an
    array of them): whole blocks of whole lane rows."""
    blocks = int(np.sum(row_blocks(np.asarray(n_rows, dtype=np.int64))))
    return blocks * ROW_BLOCK * int(plane.shape[1]) * 4 * n_planes


def reduce_rows(planes, rows, n_rows, or_sel, mask):
    """Masked popcounts and the carrier OR of the rows a batch matched,
    in a workspace that does not grow with the batch or the width.

    ``rows`` int32 ``[B, R]`` (R a multiple of ``ROW_BLOCK``) holds each
    slot's ``n_rows[b]`` real rows at its front; ``mask`` int32
    ``[B, W]``; ``or_sel`` bool ``[B, R]``, or None for no OR. One loop
    runs over the ``ROW_BLOCK``-row blocks that hold a real row, slot
    after slot: a step gathers ``[ROW_BLOCK, lanes]`` words of each
    plane through ``masked_rows`` (8 x 57 kB at 454,787 samples),
    popcounts them and ORs ``planes[0]``'s rows under ``or_sel`` into
    its slot's accumulator. So a launch reads what its queries matched,
    rounded up to blocks: a padding slot, a query that matched nothing
    and the unmatched tail of a slot gather nothing, where one gather
    of ``[B, R, lanes]`` read (and held) 64 x 1024 x 57 kB.

    Returns (``[len(planes), B, R]`` int32 popcounts, zero past each
    slot's last block; ``[B, lanes]`` int32 OR, to be folded by
    ``fold_parts``)."""
    n_slots, width = rows.shape
    lanes = planes[0].shape[1]
    blocks = row_blocks(jnp.minimum(n_rows, width)).astype(jnp.int32)
    ends = jnp.cumsum(blocks)

    def step(i, carry):
        pcs, acc = carry
        # the slot of block i: those before it end at or before i
        slot = jnp.sum(ends <= i, dtype=jnp.int32)
        at = (i - (ends[slot] - blocks[slot])) * ROW_BLOCK
        r = jax.lax.dynamic_slice(rows, (slot, at), (1, ROW_BLOCK))[0]
        m = jax.lax.dynamic_index_in_dim(mask, slot, keepdims=False)
        words = [masked_rows(plane, r, m) for plane in planes]
        pc = jnp.stack(
            [
                jnp.sum(jax.lax.population_count(g), axis=-1, dtype=jnp.int32)
                for g in words
            ]
        )
        pcs = jax.lax.dynamic_update_slice(pcs, pc[:, None, :], (0, slot, at))
        if or_sel is not None:
            take = jax.lax.dynamic_slice(or_sel, (slot, at), (1, ROW_BLOCK))[0]
            hit = jax.lax.reduce(
                jnp.where(take[:, None], words[0], jnp.int32(0)),
                np.int32(0),
                jax.lax.bitwise_or,
                dimensions=(0,),
            )
            old = jax.lax.dynamic_index_in_dim(acc, slot, keepdims=False)
            acc = jax.lax.dynamic_update_slice(
                acc, (old | hit)[None, :], (slot, 0)
            )
        return pcs, acc

    return jax.lax.fori_loop(
        0,
        ends[-1],
        step,
        (
            jnp.zeros((len(planes), n_slots, width), jnp.int32),
            jnp.zeros((n_slots, lanes), jnp.int32),
        ),
    )


@partial(jax.jit, donate_argnums=0)
def _write_rows(out, chunk, row0):
    """``out[row0 : row0 + len(chunk), : chunk.shape[1]] = chunk`` in
    place (``out`` is donated); the lanes past the chunk's width keep
    their zeros."""
    return jax.lax.dynamic_update_slice(out, chunk, (row0, 0))


def chip_of(device) -> int:
    """The chip label of a device in counters and program keys: the
    id JAX reports for it (None, the default device, is chip 0)."""
    return 0 if device is None else int(device.id)


def _lane_rows(chunk: np.ndarray, k: int) -> np.ndarray:
    """Host rows ``[m, w]`` as they cross to the device. One row to a
    lane row they cross as they are (the write pads them there). k > 1
    to a lane row they cross as the ``[ceil(m / k), 128]`` lane rows
    they fill: a view where the rows are already ``padded_words`` wide
    and m divides by k (32 words: 4m rows ARE ``[m, 128]``), else a
    zero-padded copy of this chunk alone."""
    if k == 1:
        return np.ascontiguousarray(chunk)
    m, w = chunk.shape
    p = LANES // k
    if w != p or m % k:
        padded = np.zeros((-(-m // k) * k, p), chunk.dtype)
        padded[:m, :w] = chunk
        chunk = padded
    return np.ascontiguousarray(chunk).reshape(-1, LANES)


def staged_device_put(
    a: np.ndarray, chunk_bytes: int | None, device=None
):
    """H2D upload of an ``[n, W]`` plane into its resident form
    (``resident_shape(n, W)``) on ``device`` (the zero fill, every
    chunk and the pad: nothing touches another chip), as pre-staged
    contiguous row chunks.

    One monolithic ``jnp.asarray`` of a GB-scale plane serialises
    host staging and transfer (the config7 wall: ~28 MB/s, 35.9 s for
    1.02 GB). Chunking double-buffers it: ``jax.device_put`` is
    asynchronous, so chunk i+1 streams to the device while chunk i is
    written into the zero-filled resident array, on the device and in
    place. The host array is never padded or copied whole: a wide row
    crosses unpadded and is padded by the write on the device, rows
    that share a lane row cross as the lane rows they fill
    (``_lane_rows``). The transient footprint is
    the resident array plus two chunks and one chunk's padded form (a
    monolithic upload holds the whole unpadded array and its padded
    form beside it until the write has run). ``chunk_bytes`` None/<=0
    or a small array is one chunk.
    """
    n, w = a.shape
    k = pack_factor(w)
    rows_per = max(n, 1)
    if chunk_bytes and 0 < chunk_bytes < a.nbytes:
        # whole (8, 128) tiles per write
        tile_rows = 8 * k
        rows_per = max(
            tile_rows,
            int(chunk_bytes // max(1, w * a.itemsize)) // tile_rows * tile_rows,
        )

    def put(i):
        if i >= n:
            return None
        return jax.device_put(_lane_rows(a[i : i + rows_per], k), device)

    with jax.default_device(device):
        out = jnp.zeros(resident_shape(n, w), a.dtype)
    ahead = put(0)
    for i in range(0, n, rows_per):
        chunk, ahead = ahead, put(i + rows_per)
        # one transfer ahead of the write and no more: the transfers
        # are enqueued at once, each holding its buffer on the device
        out = _write_rows(out, chunk, i // k).block_until_ready()
    return out


def sample_mask_words(
    selected_idx, n_words: int
) -> np.ndarray:
    """uint32[n_words] bit mask for a selected-sample index list — THE
    wire format every plane consumer shares (bit s%32 of word s//32).
    One scatter of bits, whatever the cohort's width: the cost is the
    selection's, and a sample named twice sets its bit once."""
    idx = np.asarray(selected_idx, dtype=np.int64).reshape(-1)
    mask = np.zeros(n_words, dtype=np.uint32)
    np.bitwise_or.at(
        mask, idx >> 5, np.uint32(1) << (idx & 31).astype(np.uint32)
    )
    return mask


class PlaneDeviceIndex:
    """Device-resident genotype planes of one shard, each in its
    resident layout (``resident_shape``): ``[n, 128 j]`` for a row of
    over 64 words, j the lane rows it takes (1 at 2504 samples, 112 at
    454,787), ``[n / k, 128]`` for a narrower one, n the rows rounded
    up to whole steps of 128.

    ``gt`` is always uploaded (sample-hit extraction needs it); the
    three count planes ride along only when the shard contains
    genotype-derived rows (any row without AC_INFO/AN_INFO) — otherwise
    the counting path never reads them (materialize_response's
    ``count_planes`` gate) and uploading them would waste HBM.
    """

    @staticmethod
    def wants_count_planes(shard: VariantIndexShard) -> bool:
        """True when the shard can need genotype-derived counting: all
        three count planes present AND at least one row without
        INFO-sourced AC/AN. ONE predicate shared by the constructor and
        the budget estimate so they can never drift."""
        flags = shard.cols["flags"]
        return bool(
            shard.has_count_planes
            and (
                ((flags & FLAG.AC_INFO) == 0).any()
                or ((flags & FLAG.AN_INFO) == 0).any()
            )
        )

    def __init__(
        self,
        shard: VariantIndexShard,
        upload_chunk_bytes: int | None = 256 * 1024 * 1024,
        device=None,
    ):
        if shard.gt_bits is None:
            raise ValueError("shard has no genotype planes")
        # the chip every plane is committed to, and so the chip every
        # program that reads them runs on (None: the default device)
        self.device = device
        # n_rows x n_words is the logical shape (the mask's and
        # or_words' width); the resident arrays are resident_shape of it
        self.n_rows, self.n_words = shard.gt_bits.shape
        self.has_counts = self.wants_count_planes(shard)

        # no padding row: padded gather slots point at row 0 — their
        # count outputs are trimmed by the caller and their OR lanes
        # carry or_sel=0, so the value read is never observed. (An
        # appended zero row would cost a full host-side copy of the
        # largest array in the system.)
        def up(a):
            return staged_device_put(
                a.view(np.int32), upload_chunk_bytes, device
            )

        self.gt = up(shard.gt_bits)
        if self.has_counts:
            self.gt2 = up(shard.gt_bits2)
            self.tok1 = up(shard.tok_bits1)
            self.tok2 = up(shard.tok_bits2)
        else:
            self.gt2 = self.tok1 = self.tok2 = None

    def planes(self) -> list:
        """The resident arrays, ``gt`` first."""
        return [
            a for a in (self.gt, self.gt2, self.tok1, self.tok2)
            if a is not None
        ]

    def nbytes_hbm(self) -> int:
        """HBM bytes of the resident planes, exactly: each is held
        ``resident_shape(n_rows, n_words)`` int32. What the budget gate
        reserved before the upload (``estimate_hbm``)."""
        return sum(int(a.nbytes) for a in self.planes())

    def logical_bytes(self) -> int:
        """Bytes of the planes' own words, ``n_rows x n_words x 4``
        each: what ``nbytes_hbm`` would be with no padding at all."""
        return self.n_rows * self.n_words * 4 * len(self.planes())

    @staticmethod
    def estimate_hbm(shard: VariantIndexShard) -> int:
        """Upload-free HBM estimate for the capacity gate (same
        count-plane predicate as the constructor)."""
        if shard.gt_bits is None:
            return 0
        lane_rows, lanes = resident_shape(*shard.gt_bits.shape)
        has_counts = PlaneDeviceIndex.wants_count_planes(shard)
        return lane_rows * lanes * 4 * (4 if has_counts else 1)


@partial(jax.jit, static_argnames=("R", "with_counts", "with_or"))
def _plane_stats(
    gt, gt2, tok1, tok2, rows, n_rows, or_sel, mask, *, R, with_counts,
    with_or
):
    """[R,4] per-row masked popcounts + [W] OR of gt&mask over or_sel.

    ``rows`` int32[R] with the ``n_rows`` (an int32 scalar) real rows at
    its front, ``or_sel`` int32[R] 0/1, ``mask`` int32[W]: the planes
    lie in their resident layout (``PlaneDeviceIndex``) and are read
    block by block through ``reduce_rows``, the real rows alone.
    Popcount columns:
    0=gt, 1=gt2, 2=tok1, 3=tok2 (count columns zero when the plane set
    has no count planes; every column zero past the last real block)."""
    pcs, acc = reduce_rows(
        (gt, gt2, tok1, tok2) if with_counts else (gt,),
        rows[None, :],
        n_rows[None],
        (or_sel != 0)[None, :] if with_or else None,
        mask[None, :],
    )
    zero = jnp.zeros((R,), jnp.int32)
    cols = [pcs[p, 0] if p < pcs.shape[0] else zero for p in range(4)]
    return jnp.stack(cols, axis=1), fold_parts(acc[0], mask.shape[0])


def plane_row_stats(
    pindex: PlaneDeviceIndex,
    rows: np.ndarray,
    selected_mask_words: np.ndarray | None,
    *,
    or_sel: np.ndarray | None = None,
    with_counts: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Device masked plane reductions for a matched-row set.

    Returns ``(counts[len(rows), 4] int64, or_words[W] uint32)``.
    ``or_sel`` restricts the gt OR-reduction to a row subset (the
    caller's exact ``grp >= k0`` selection); None ORs nothing.
    ``with_counts`` defaults to the plane set's capability."""
    R = len(rows)
    if with_counts is None:
        with_counts = pindex.has_counts
    top = _R_TIERS[-1]
    if R > top:
        # chunk through the fixed top tier: counts concatenate, the OR
        # words fold on host (compile cache stays bounded)
        counts_parts = []
        or_acc = None
        for a in range(0, R, top):
            sl = slice(a, min(a + top, R))
            cnt, ow = plane_row_stats(
                pindex,
                rows[sl],
                selected_mask_words,
                or_sel=None if or_sel is None else or_sel[sl],
                with_counts=with_counts,
            )
            counts_parts.append(cnt)
            or_acc = ow if or_acc is None else (or_acc | ow)
        return (
            np.concatenate(counts_parts),
            or_acc
            if or_acc is not None
            else np.zeros(pindex.n_words, np.uint32),
        )
    tier = next(t for t in _R_TIERS if R <= t)
    # pad slots target row 0: counts are trimmed to [:R], OR lanes carry
    # or_sel=0, so the padded reads are never observed
    rows_p = np.zeros(tier, np.int32)
    rows_p[:R] = rows
    sel_p = np.zeros(tier, np.int32)
    if or_sel is not None:
        sel_p[:R] = np.asarray(or_sel, dtype=np.int32)
    if selected_mask_words is None:
        mask = np.full(pindex.n_words, 0xFFFFFFFF, np.uint32)
    else:
        mask = np.asarray(selected_mask_words, dtype=np.uint32)
    t0 = time.perf_counter()
    counts, or_words = _plane_stats(
        pindex.gt,
        pindex.gt2 if with_counts else pindex.gt,
        pindex.tok1 if with_counts else pindex.gt,
        pindex.tok2 if with_counts else pindex.gt,
        jax.device_put(rows_p, pindex.device),
        jax.device_put(np.int32(R), pindex.device),
        jax.device_put(sel_p, pindex.device),
        jax.device_put(mask.view(np.int32), pindex.device),
        R=tier,
        with_counts=with_counts,
        with_or=or_sel is not None,
    )
    # flight-recorder seam (the scatter seam feeds the historical
    # N_DISPATCHES property). The old `_sk.N_DISPATCHES += 1` here was
    # worse than the racy read-modify-write the lint bans: the read
    # went through scatter_kernel's PEP 562 recorder property and the
    # write then planted a REAL module attribute, permanently
    # shadowing the recorder behind a frozen snapshot for every later
    # reader in the process.
    seq = record_device_launch(
        "plane",
        seam="scatter",
        tier=tier,
        specs_real=R,
        specs_padded=tier,
        # its specs are rows: one dataset's, for one request
        targets=1,
        launch_ms=(time.perf_counter() - t0) * 1e3,
        chip=chip_of(pindex.device),
    )
    note_device_stage(
        seq,
        gather_bytes=gathered_bytes(
            pindex.gt, R, 4 if with_counts else 1
        ),
    )
    counts, or_words = jax.device_get((counts, or_words))
    return (
        np.asarray(counts)[:R].astype(np.int64),
        np.asarray(or_words).view(np.uint32),
    )
