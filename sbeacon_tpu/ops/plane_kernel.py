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
  128: ``[n, Wp]``, 512 B/row/plane at 2504 samples. A narrower row is
  zero-padded to p words, p the least power of two >= W, and k = 128 // p
  rows share one lane row: ``[ceil(n / k), 128]``, 128 B/row/plane at
  1000 samples (``pack_factor``, ``resident_shape``). The count planes
  (gt2/tok1/tok2) are uploaded only when the
  shard has genotype-derived rows at all — INFO-sourced corpora (the
  common cohort-VCF case, and the bench corpus) only ever touch ``gt``
  for sample-hit extraction, so only it occupies HBM.
- ``plane_row_stats`` gathers the matched rows' plane words, ANDs the
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
from ..telemetry import record_device_launch

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


def resident_shape(n_rows: int, n_words: int) -> tuple[int, int]:
    """Shape of the resident array of an ``[n_rows, n_words]`` plane:
    ``[ceil(n_rows / k), k * padded_words]``; row r lies in lane row
    ``r // k`` at words ``(r % k) * padded_words`` onward (the rows past
    the last in its lane row are zeros nobody reads)."""
    k = pack_factor(n_words)
    return -(-n_rows // k), k * padded_words(n_words)


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
    wire format every plane consumer shares (bit s%32 of word s//32)."""
    mask = np.zeros(n_words, dtype=np.uint32)
    for si in selected_idx:
        mask[si // 32] |= np.uint32(1 << (si % 32))
    return mask


class PlaneDeviceIndex:
    """Device-resident genotype planes of one shard.

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
    gt, gt2, tok1, tok2, rows, or_sel, mask, *, R, with_counts, with_or
):
    """[R,4] per-row masked popcounts + [W] OR of gt&mask over or_sel.

    ``rows`` int32[R] (padding slots point at row 0; callers discard
    their outputs), ``or_sel`` int32[R] 0/1, ``mask`` int32[W]: the
    planes lie in their resident layout (``PlaneDeviceIndex``) and are
    read through ``masked_rows``. Popcount columns:
    0=gt, 1=gt2, 2=tok1, 3=tok2 (count columns zero when the plane set
    has no count planes)."""
    n_words = mask.shape[0]

    def pc(g):
        return jnp.sum(jax.lax.population_count(g), axis=1).astype(jnp.int32)

    g = masked_rows(gt, rows, mask)  # [R, lanes]
    pc_gt = pc(g)
    zero = jnp.zeros_like(pc_gt)
    if with_counts:
        cols = [pc_gt] + [
            pc(masked_rows(plane, rows, mask)) for plane in (gt2, tok1, tok2)
        ]
    else:
        cols = [pc_gt, zero, zero, zero]
    counts = jnp.stack(cols, axis=1)
    if with_or:
        or_words = fold_parts(
            jax.lax.reduce(
                jnp.where(or_sel[:, None] != 0, g, jnp.int32(0)),
                np.int32(0),
                jax.lax.bitwise_or,
                dimensions=(0,),
            ),
            n_words,
        )
    else:
        or_words = jnp.zeros((n_words,), jnp.int32)
    return counts, or_words


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
    record_device_launch(
        "plane",
        seam="scatter",
        tier=tier,
        specs_real=R,
        specs_padded=tier,
        launch_ms=(time.perf_counter() - t0) * 1e3,
        chip=chip_of(pindex.device),
    )
    counts, or_words = jax.device_get((counts, or_words))
    return (
        np.asarray(counts)[:R].astype(np.int64),
        np.asarray(or_words).view(np.uint32),
    )
