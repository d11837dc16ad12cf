"""VariantEngine: the query orchestrator.

Replaces the reference's entire distributed query engine — the 500-thread
dataset scatter (reference: shared_resources/variantutils/search_variants.py:
77-118), the splitQuery 10kb-window cross-product (lambda/splitQuery/
lambda_function.py:38-71), the per-region performQuery lambdas, and the
DynamoDB fan-in counters (dynamodb/variant_queries.py:45-59) — with direct
kernel dispatch: every (dataset, vcf) pair pinned to the engine answers the
whole query range in one windowed kernel invocation, and fan-in is just
array aggregation.

Response materialisation reproduces the reference loop's *cumulative*
accumulator semantics (performQuery/search_variants.py:229-254): boolean
granularity truncates at the first record that flips ``exists``;
include_details=False stops before adding that record's AN; sample hits only
accumulate once the cumulative call count is positive. The kernel returns
order-preserving matched row ids, so these order-sensitive semantics are
recovered exactly on host.

Overflow handling: a query whose candidate window exceeds ``window_cap``
rows (or whose matches exceed ``record_cap``) falls back to
``host_match_rows`` — a vectorised numpy twin of the device kernel with no
shape caps and byte-exact (blob, not hash) allele comparison.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .config import BeaconConfig
from .harness.faults import fault_point
from .index.columnar import FLAG, VariantIndexShard
from .ops import make_device_index, run_queries_auto
from .ops.kernel import QuerySpec, encode_queries
from .payloads import VariantQueryPayload, VariantSearchResponse
from .plan import plan_stage
from .response_cache import (
    ResponseCache,
    response_cache_key,
    response_cache_scope,
)
from .telemetry import (
    DEFAULT_MAX_LABEL_VALUES,
    OVERFLOW_LABEL,
    annotate,
    charge_cost,
    current_context,
    device_warmup_phase,
    publish_event,
    record_device_fallback,
    request_context,
)
from .utils.chrom import chromosome_code
from .utils.trace import span, stage, tracer

# uppercase LUT for vectorised case-insensitive byte compares
_UPPER = np.arange(256, dtype=np.uint8)
_UPPER[97:123] -= 32


def _blob_eq(
    blob: np.ndarray,
    off: np.ndarray,
    idx: np.ndarray,
    lens: np.ndarray,
    want: bytes,
    *,
    upper: bool,
    prefix: bool = False,
    wildcard_n: bool = False,
) -> np.ndarray:
    """Vectorised per-row compare of blob slices against one query string.

    Equality mode: row bytes (uppercased when ``upper``) == want.
    Prefix mode: row starts with ``want``.
    Wildcard mode: an 'N' in ``want`` accepts any of A/C/G/T/N at that
    position (the selected-samples ref regex, reference
    search_variants_in_samples.py:87-91).
    No per-row Python: rows are first narrowed by length, then compared as a
    2D fixed-width gather.
    """
    wlen = len(want)
    out = np.zeros(len(idx), dtype=bool)
    cand = lens >= wlen if prefix else lens == wlen
    if not cand.any() or wlen == 0:
        if wlen == 0:
            out[:] = True if prefix else lens == 0
        return out
    rows = idx[cand]
    starts = off[rows].astype(np.int64)
    mat = blob[starts[:, None] + np.arange(wlen)]
    if upper:
        mat = _UPPER[mat]
    wanted = np.frombuffer(want, dtype=np.uint8)
    eq = mat == wanted
    if wildcard_n:
        acgtn = np.isin(mat, np.frombuffer(b"ACGTN", dtype=np.uint8))
        eq |= (wanted == ord("N")) & acgtn
    out[cand] = eq.all(axis=1)
    return out


def host_match_rows(
    shard: VariantIndexShard, q: QuerySpec, *, ref_wildcard: bool = False
) -> np.ndarray:
    """All matching row ids, numpy-vectorised, no caps, byte-exact alleles.

    ``ref_wildcard`` switches the ref compare to the selected-samples
    N-wildcard semantics."""
    c = shard.cols
    code = chromosome_code(q.chrom)
    lo = int(shard.chrom_offsets[code])
    hi = int(shard.chrom_offsets[code + 1])
    if lo == hi:
        return np.empty(0, dtype=np.int64)
    pos = c["pos"][lo:hi]
    a = int(np.searchsorted(pos, q.start_min, side="left"))
    b = int(np.searchsorted(pos, q.start_max, side="right"))
    if a >= b:
        return np.empty(0, dtype=np.int64)
    # cost attribution (ISSUE 11): the candidate bracket is exactly
    # the rows this scan walks — charged to the ambient request's
    # CostVector (or the unattributed residue off-request)
    charge_cost(host_rows=b - a)
    sl = slice(lo + a, lo + b)
    idx = np.arange(lo + a, lo + b)

    rec_end = c["rec_end"][sl]
    ok = (q.end_min <= rec_end) & (rec_end <= q.end_max)

    if q.reference_bases is not None and q.reference_bases != "N":
        ok &= _blob_eq(
            shard.ref_blob,
            shard.ref_off,
            idx,
            c["ref_len"][sl],
            q.reference_bases.encode(),
            upper=True,
            wildcard_n=ref_wildcard,
        )

    alt_len = c["alt_len"][sl]
    max_len = 2**31 - 1 if q.variant_max_length < 0 else q.variant_max_length
    ok &= (q.variant_min_length <= alt_len) & (alt_len <= max_len)

    flags = c["flags"][sl]
    f = lambda bit: (flags & bit) != 0
    if q.alternate_bases is None:
        sym = f(FLAG.SYMBOLIC)
        k = c["ref_repeat_k"][sl]
        ref_len = c["ref_len"][sl]
        vt = q.variant_type
        # '<' + str(vt): None formats to '<None' and matches nothing
        # (reference performQuery/search_variants.py:54)
        vpref = ("<" + str(vt)).encode()
        pm = _blob_eq(
            shard.alt_blob,
            shard.alt_off,
            idx,
            alt_len,
            vpref,
            upper=False,
            prefix=True,
        )
        if vt == "DEL":
            alt_ok = np.where(sym, pm | f(FLAG.CN0), alt_len < ref_len)
        elif vt == "INS":
            alt_ok = np.where(sym, pm, alt_len > ref_len)
        elif vt == "DUP":
            alt_ok = np.where(
                sym, pm | (f(FLAG.CN_PREFIX) & ~f(FLAG.CN0) & ~f(FLAG.CN1)), k >= 2
            )
        elif vt == "DUP:TANDEM":
            alt_ok = np.where(sym, pm | f(FLAG.CN2), k == 2)
        elif vt == "CNV":
            alt_ok = np.where(
                sym,
                pm | f(FLAG.CN_PREFIX) | f(FLAG.DEL_PREFIX) | f(FLAG.DUP_PREFIX),
                f(FLAG.DOT) | (k >= 1),
            )
        else:
            alt_ok = sym & pm
        ok &= alt_ok.astype(bool)
    elif q.alternate_bases == "N":
        ok &= f(FLAG.SINGLE_BASE)
    else:
        ok &= _blob_eq(
            shard.alt_blob,
            shard.alt_off,
            idx,
            alt_len,
            q.alternate_bases.encode(),
            upper=True,
        )
    return idx[ok]


def shard_regions(shard: VariantIndexShard) -> list[tuple[str, int, int]]:
    """Per-chromosome coordinate envelope ``[(chrom, lo, hi), ...]`` of
    a shard's rows — the scope a delta publish invalidates the response
    cache with. ``hi`` covers both start positions and record ends, so
    any query bracket that could match a row overlaps its envelope."""
    from .utils.chrom import CODE_TO_CHROMOSOME

    out: list[tuple[str, int, int]] = []
    off = shard.chrom_offsets
    pos = shard.cols["pos"]
    rec_end = shard.cols["rec_end"]
    for code in range(len(off) - 1):
        lo, hi = int(off[code]), int(off[code + 1])
        if lo == hi:
            continue
        chrom = CODE_TO_CHROMOSOME.get(code, "")
        out.append(
            (
                chrom,
                int(pos[lo:hi].min()),
                int(max(pos[lo:hi].max(), rec_end[lo:hi].max())),
            )
        )
    return out


def _popcount_masked(plane_row: np.ndarray, mask: np.ndarray) -> int:
    return sum(int(w).bit_count() for w in (plane_row & mask))


def materialize_response_loop(
    shard: VariantIndexShard,
    rows: np.ndarray,
    payload: VariantQueryPayload,
    *,
    chrom_label: str,
    dataset_id: str = "",
    vcf_location: str = "",
    selected_idx: list[int] | None = None,
) -> VariantSearchResponse:
    """Reference implementation of row-id materialisation (per-record
    Python loop). Kept as the executable spec of the cumulative-order
    semantics; serving uses the vectorised ``materialize_response``
    below, which is fuzz-tested against this function
    (tests/test_engine.py) — at real-scale record queries the loop's
    per-row popcounts were the host-side wall (VERDICT r2 weak #7).

    ``selected_idx`` activates the selected-samples leaf (reference
    search_variants_in_samples.py): INFO-sourced AC/AN stay full-cohort
    (bcftools --samples leaves INFO untouched) while genotype-derived
    counts, variant listing and sample-hit extraction are restricted to the
    masked samples; returned sample indices are positions in the *selected*
    list, as the subset bcftools output would yield.
    """
    c = shard.cols
    rows = np.asarray(rows, dtype=np.int64)
    granularity = payload.requested_granularity
    include_details = payload.include_details

    mask = None
    if selected_idx is not None and shard.gt_bits is not None:
        from .ops.plane_kernel import sample_mask_words

        mask = sample_mask_words(selected_idx, shard.gt_bits.shape[1])
    # restricted genotype-derived counting needs the full plane set; a
    # shard persisted before the count planes existed degrades to the
    # full-cohort baked counts (sample extraction still restricts)
    count_planes = mask is not None and shard.has_count_planes
    sel_set = set(selected_idx or [])

    def _overflow_extra(which: str, row: int) -> int:
        return sum(
            v - 2
            for s, v in shard.overflow_map(which).get(row, ())
            if s in sel_set
        )

    exists = False
    call_count = 0
    all_alleles = 0
    variants: list[str] = []
    sample_indices: set[int] = set()

    # group matched rows by record, in row (=position/scan) order
    i = 0
    n = len(rows)
    while i < n:
        j = i
        rid = c["rec_id"][rows[i]]
        while j < n and c["rec_id"][rows[j]] == rid:
            j += 1
        rec_rows = rows[i:j]
        i = j

        for r in rec_rows:
            r = int(r)
            if count_planes and not (c["flags"][r] & FLAG.AC_INFO):
                rc = (
                    _popcount_masked(shard.gt_bits[r], mask)
                    + _popcount_masked(shard.gt_bits2[r], mask)
                    + _overflow_extra("gt", r)
                )
                call_count += rc
                if rc:
                    variants.append(shard.variant_string(r, chrom_label))
            else:
                call_count += int(c["ac"][r])
                if c["ac"][r] != 0:
                    variants.append(shard.variant_string(r, chrom_label))

        if call_count:
            exists = True
            if not include_details:
                break  # before this record's AN is added (reference :231)
            if (
                granularity in ("record", "aggregated")
                and payload.include_samples
                and shard.gt_bits is not None
            ):
                for r in rec_rows:
                    if mask is None:
                        sample_indices.update(shard.row_samples(int(r)))
                    else:
                        bits = shard.gt_bits[int(r)]
                        sample_indices.update(
                            k
                            for k, si in enumerate(selected_idx)
                            if bits[si // 32] >> np.uint32(si % 32) & 1
                        )

        r0 = int(rec_rows[0])
        if count_planes and not (c["flags"][r0] & FLAG.AN_INFO):
            all_alleles += (
                _popcount_masked(shard.tok_bits1[r0], mask)
                + _popcount_masked(shard.tok_bits2[r0], mask)
                + _overflow_extra("tok", r0)
            )
        else:
            all_alleles += int(c["an"][r0])

        if granularity == "boolean" and exists:
            break

    resolved = []
    if (
        granularity in ("record", "aggregated")
        and payload.include_samples
        and shard.meta.get("sample_names")
    ):
        names = shard.meta["sample_names"]
        if selected_idx is not None:
            names = [names[si] for si in selected_idx]
        resolved = [s for k, s in enumerate(names) if k in sample_indices]

    return VariantSearchResponse(
        dataset_id=dataset_id,
        vcf_location=vcf_location,
        exists=exists,
        all_alleles_count=all_alleles,
        call_count=call_count,
        variants=variants,
        sample_indices=sorted(sample_indices),
        sample_names=resolved,
    )


def _popcounts(words: np.ndarray, mask: np.ndarray | None) -> np.ndarray:
    """Per-row popcount of (words & mask): [k, w] uint32 -> [k] int64."""
    if mask is not None:
        words = words & mask
    return np.bitwise_count(words).sum(axis=1, dtype=np.int64)


def _overflow_extras(
    shard: VariantIndexShard,
    which: str,
    target_rows: np.ndarray,
    sel_mask: np.ndarray,
) -> np.ndarray:
    """[len(target_rows)] extra copies beyond the 2-bit planes for the
    given rows, restricted to selected samples (ploidy>2 side table)."""
    out = np.zeros(len(target_rows), dtype=np.int64)
    ov = shard.gt_overflow if which == "gt" else shard.tok_overflow
    if ov is None or not len(ov) or not len(target_rows):
        return out
    hit = np.isin(ov[:, 0], target_rows) & sel_mask[ov[:, 1]]
    if not hit.any():
        return out
    ov = ov[hit]
    order = np.argsort(target_rows, kind="stable")
    pos = order[np.searchsorted(target_rows[order], ov[:, 0])]
    np.add.at(out, pos, ov[:, 2] - 2)
    return out


class SampleSelection:
    """The samples a request selected, resolved against ONE shard, once:
    their positions in the planes' bit order (in the order the request
    named them; a name the shard does not know is dropped), the mask
    words every plane consumer shares, and their names. Every step from
    here to the response is numpy over the selection (18,191 of 454,787
    in a biobank-width cohort), never Python over it or the cohort."""

    __slots__ = ("idx", "mask", "names")

    def __init__(self, shard: VariantIndexShard, idx):
        from .ops.plane_kernel import sample_mask_words

        self.idx = np.asarray(idx, dtype=np.int64).reshape(-1)
        self.mask = (
            sample_mask_words(self.idx, shard.gt_bits.shape[1])
            if shard.gt_bits is not None
            else None
        )
        self.names = (
            shard.sample_name_array()[self.idx]
            if shard.meta.get("sample_names")
            else None
        )

    def __len__(self) -> int:
        return len(self.idx)

    def carriers(self, words: np.ndarray) -> np.ndarray:
        """Positions IN THE SELECTION (ascending) of the samples whose
        bit is set in ``words`` (uint32 ``[n_words]``, an OR of carrier
        rows): what ``bcftools --samples`` output would index."""
        shift = (self.idx & 31).astype(np.uint32)
        return np.flatnonzero((words[self.idx >> 5] >> shift) & np.uint32(1))

    def member_mask(self, n_samples: int) -> np.ndarray:
        """bool ``[n_samples]``: True at a selected sample (the ploidy
        side tables are joined against it)."""
        out = np.zeros(max(n_samples, 1), dtype=bool)
        out[self.idx] = True
        return out


def materialize_response(
    shard: VariantIndexShard,
    rows: np.ndarray,
    payload: VariantQueryPayload,
    *,
    chrom_label: str,
    dataset_id: str = "",
    vcf_location: str = "",
    selected_idx: "list[int] | SampleSelection | None" = None,
    plane_index=None,
    fused=None,
) -> VariantSearchResponse:
    """Vectorised row-id materialisation (cumulative-order semantics).

    Same contract as :func:`materialize_response_loop` (the executable
    spec), computed without per-row Python: per-row call contributions in
    one ``np.bitwise_count`` pass, record grouping via ``reduceat``, the
    reference's cumulative truncation points (first record that flips
    ``exists``) from one cumsum, and sample-hit extraction as a single
    OR-reduction over the genotype plane slice. Matched-variant strings
    remain a comprehension over matched rows only — they ARE the response
    payload, so their count is already bounded by what the client asked
    to receive.

    ``plane_index`` (an ``ops.plane_kernel.PlaneDeviceIndex``) moves the
    plane reads on-device: per-row masked popcounts and the sample-hit
    OR run as one-or-two jitted gather programs over HBM-resident
    planes instead of numpy over the ~n_rows x n_samples/8 host arrays.
    The truncation/AN/overflow semantics are computed on host from the
    device-returned scalars and are bit-identical to the host path (the
    ploidy>2 overflow side tables stay host-applied either way).

    ``fused`` short-circuits BOTH plane reads with outputs the fused
    match+planes kernel already computed in the match dispatch
    (``scatter_kernel.run_selected_scattered`` — zero additional device
    calls here): a ``(pc_call, pc_tok, or_words)`` triple where
    pc_call/pc_tok are per-row masked popcounts aligned with ``rows``
    and or_words is the sample-hit OR over the grp>=k0 subset.
    Takes precedence over ``plane_index``.
    """
    c = shard.cols
    rows = np.asarray(rows, dtype=np.int64)
    granularity = payload.requested_granularity
    include_details = payload.include_details

    # the selection arrives resolved from the engine (one a request and
    # dataset: the mask the launch took is the mask used here); a plain
    # position list (tests, the mesh dryrun) is resolved on the spot
    sel = selected_idx
    if sel is not None and not isinstance(sel, SampleSelection):
        sel = SampleSelection(shard, sel)
    mask = sel.mask if sel is not None else None
    count_planes = mask is not None and shard.has_count_planes

    n = len(rows)
    if n == 0:
        return VariantSearchResponse(
            dataset_id=dataset_id,
            vcf_location=vcf_location,
            exists=False,
            all_alleles_count=0,
            call_count=0,
            variants=[],
            sample_indices=[],
            sample_names=[],
        )

    rec = c["rec_id"][rows]
    new_grp = np.empty(n, dtype=bool)
    new_grp[0] = True
    np.not_equal(rec[1:], rec[:-1], out=new_grp[1:])
    starts = np.flatnonzero(new_grp)  # index into rows of each record
    grp_of = np.cumsum(new_grp) - 1  # record-group index per row
    n_grp = len(starts)

    # per-row call contribution (the loop's rc)
    ac_rows = c["ac"][rows].astype(np.int64)
    rc = ac_rows.copy()
    r0 = rows[starts]
    gt_rows = (
        np.flatnonzero((c["flags"][rows] & FLAG.AC_INFO) == 0)
        if count_planes
        else np.zeros(0, np.int64)
    )
    tok_grps = (
        np.flatnonzero((c["flags"][r0] & FLAG.AN_INFO) == 0)
        if count_planes
        else np.zeros(0, np.int64)
    )
    dev_counts = None
    if (
        fused is None
        and plane_index is not None
        and plane_index.has_counts
        and (len(gt_rows) or len(tok_grps))
    ):
        # ONE device call covers both popcount target sets (matched
        # rows needing genotype-derived AC, record-first rows needing
        # token-derived AN)
        from .ops.plane_kernel import plane_row_stats

        cat = np.concatenate([rows[gt_rows], r0[tok_grps]])
        dev_counts, _ = plane_row_stats(plane_index, cat, mask)
    sel_mask = (
        sel.member_mask(len(shard.meta.get("sample_names", [])))
        if count_planes and (len(gt_rows) or len(tok_grps))
        else None
    )
    if count_planes and len(gt_rows):
        rr = rows[gt_rows]
        extras = _overflow_extras(shard, "gt", rr, sel_mask)
        if fused is not None:
            rc[gt_rows] = fused[0][gt_rows].astype(np.int64) + extras
        elif dev_counts is not None:
            pc = dev_counts[: len(gt_rows)]
            rc[gt_rows] = pc[:, 0] + pc[:, 1] + extras
        else:
            rc[gt_rows] = (
                _popcounts(shard.gt_bits[rr], mask)
                + _popcounts(shard.gt_bits2[rr], mask)
                + extras
            )

    rc_grp = np.add.reduceat(rc, starts)
    cum = np.cumsum(rc_grp)
    exists = bool(cum[-1] > 0)
    k0 = int(np.argmax(cum > 0)) if exists else n_grp - 1

    # per-record AN (from each record's first row)
    an_grp = c["an"][r0].astype(np.int64)
    if count_planes and len(tok_grps):
        rr = r0[tok_grps]
        extras = _overflow_extras(shard, "tok", rr, sel_mask)
        if fused is not None:
            an_grp[tok_grps] = (
                fused[1][starts[tok_grps]].astype(np.int64) + extras
            )
        elif dev_counts is not None:
            tk = dev_counts[len(gt_rows) :]
            an_grp[tok_grps] = tk[:, 2] + tk[:, 3] + extras
        else:
            an_grp[tok_grps] = (
                _popcounts(shard.tok_bits1[rr], mask)
                + _popcounts(shard.tok_bits2[rr], mask)
                + extras
            )

    # cumulative truncation: which records the loop would process
    if not exists:
        last_grp = n_grp - 1  # all records; AN accumulates for each
        call_count = 0
        an_through = n_grp  # exclusive end
    elif not include_details:
        last_grp = k0
        call_count = int(cum[k0])
        an_through = k0  # breaks BEFORE adding record k0's AN
    elif granularity == "boolean":
        last_grp = k0
        call_count = int(cum[k0])
        an_through = k0 + 1  # boolean breaks AFTER the AN add
    else:
        last_grp = n_grp - 1
        call_count = int(cum[-1])
        an_through = n_grp
    all_alleles = int(an_grp[:an_through].sum())

    # matched-variant strings, row order, records <= last_grp only
    keep = (rc != 0) & (grp_of <= last_grp)
    vrows = rows[keep]
    pos_v = c["pos"][vrows]
    ro, re = shard.ref_off[vrows], shard.ref_off[vrows + 1]
    ao, ae = shard.alt_off[vrows], shard.alt_off[vrows + 1]
    vt = shard.vt_codes[vrows]
    vocab = shard.meta["vt_vocab"]
    rb, ab = shard.ref_blob, shard.alt_blob
    variants = [
        (
            f"{chrom_label}\t{pos_v[i]}"
            f"\t{rb[ro[i]:re[i]].tobytes().decode()}"
            f"\t{ab[ao[i]:ae[i]].tobytes().decode()}\t{vocab[vt[i]]}"
        )
        for i in range(len(vrows))
    ]

    # sample-hit extraction: all rows of records from k0 onward
    sample_indices: list[int] = []
    resolved: list[str] = []
    if (
        exists
        and include_details
        and granularity in ("record", "aggregated")
        and payload.include_samples
        and shard.gt_bits is not None
    ):
        srows = rows[grp_of >= k0]
        if fused is not None:
            # the fused kernel already OR-reduced the grp>=k0 subset
            # in the match dispatch (rc positivity — and therefore k0
            # and the subset — is ploidy-extras-invariant)
            agg = np.asarray(fused[2], dtype=np.uint32)
        elif plane_index is not None:
            # device OR-reduction over the exact grp>=k0 subset (k0 is
            # host-known by now in every case, so one dispatch is exact)
            from .ops.plane_kernel import plane_row_stats

            _cnts, agg = plane_row_stats(
                plane_index,
                srows,
                mask,
                or_sel=np.ones(len(srows), np.int32),
                with_counts=False,
            )
        else:
            agg = np.bitwise_or.reduce(shard.gt_bits[srows], axis=0)
        # a selection reads its own samples' bits and no others
        if sel is not None:
            hits = sel.carriers(agg)
        else:
            hits = np.flatnonzero(
                np.unpackbits(agg.view(np.uint8), bitorder="little")
            )
        names = (
            sel.names if sel is not None else shard.sample_name_array()
        )
        if (
            names is not None
            and len(names)
            and granularity in ("record", "aggregated")
            and payload.include_samples
        ):
            resolved = names[hits[hits < len(names)]].tolist()
        sample_indices = hits.tolist()

    return VariantSearchResponse(
        dataset_id=dataset_id,
        vcf_location=vcf_location,
        exists=exists,
        all_alleles_count=all_alleles,
        call_count=call_count,
        variants=variants,
        sample_indices=sample_indices,
        sample_names=resolved,
    )


#: what a dataset the launch says matched nothing has in hand
_NO_ROWS = np.zeros(0, dtype=np.int64)


def _launched_rows(findex, routes, res, record_cap: int) -> dict:
    """``{key: shard-local row ids | None}`` of ONE stacked launch
    (the fused stack's, the L0 mini-index's) over ``routes``
    ``[(key, shard id)]``, decided by one vector test of the launch's
    own counts: None where it overflowed its window or matched more
    than ``record_cap`` rows (the caller host-matches that shard
    uncapped), the shared empty array where it matched nothing, and
    only for the rest a slice of its rows: the first ``n_matched`` of
    them (``_query_one`` returns the matched row ids first, ascending,
    then -1), so the pick costs what matched, not ``record_cap``
    words. A point query over 32 datasets hits one of them or none."""
    n = len(routes)
    n_matched = np.asarray(res.n_matched[:n])
    host = np.asarray(res.overflow[:n], dtype=bool) | (n_matched > record_cap)
    out = dict.fromkeys([key for key, _sid in routes], _NO_ROWS)
    for i in np.flatnonzero(host | (n_matched > 0)).tolist():
        key, sid = routes[i]
        out[key] = (
            None
            if host[i]
            else findex.to_local_rows(res.rows[i, : n_matched[i]], sid)
        )
    return out


def _no_match(target) -> VariantSearchResponse:
    """What ``materialize_response`` answers over no rows: the response
    of a target whose launch matched nothing."""
    return VariantSearchResponse(dataset_id=target[0], vcf_location=target[1])


def _device_fallback(site: str, msg: str, *args) -> None:
    """A device path failed inside an ``except`` block and another
    path serves: log the traceback AND tick ``device.fallbacks{site}``
    — production keeps answering, but never silently."""
    record_device_fallback(site)
    logging.getLogger(__name__).exception(msg, *args)


def register_delta_metrics(registry, supplier) -> None:
    """The ingest-while-serving delta-tail series. ``supplier`` returns
    :meth:`VariantEngine.delta_metrics` (or ``{}`` on engines without a
    delta registry) — the series exist as zeros on every deployment
    shape so the catalogue stays stable."""

    def field(name):
        def collect():
            stats = supplier() or {}
            return stats.get(name, 0)

        return collect

    registry.counter(
        "ingest.delta_publishes",
        "delta shards published for immediate serving",
        fn=field("publishes"),
    )
    registry.gauge(
        "ingest.delta_shards",
        "delta shards currently standing (awaiting compaction)",
        fn=field("shards"),
    )
    registry.counter(
        "ingest.l0_builds",
        "delta-tail L0 mini-index builds (tail stacked past the "
        "depth/row threshold)",
        fn=field("l0_builds"),
    )
    registry.counter(
        "ingest.l0_served_queries",
        "queries whose delta-tail targets rode the L0 mini-index "
        "launch instead of per-shard host scans",
        fn=field("l0_served"),
    )
    # per-key build attribution (ISSUE 20): the engine bounds its own
    # key set at DEFAULT_MAX_LABEL_VALUES (overflow collapses to the
    # sentinel), so the fn-backed series honours the cardinality cap
    # without the registry guard
    registry.counter(
        "ingest.l0_key_builds",
        "per-(dataset/vcf) L0 block stacks — a publish to one key "
        "rebuilds only that key's block",
        label="key",
        fn=field("l0_key_builds"),
    )
    registry.counter(
        "ingest.l0_block_reuses",
        "standing L0 blocks reused as-is by a composite rebuild "
        "(untouched keys are never restacked)",
        fn=field("l0_block_reuses"),
    )


class VariantEngine:
    """Holds device-resident indexes and answers variant queries.

    One engine instance owns the indexes pinned to the local device(s); the
    dataset-shard mesh dispatch lives in ``parallel/`` and composes engines.
    """

    def __init__(self, config: BeaconConfig | None = None):
        self.config = config or BeaconConfig()
        # (dataset_id, vcf_location) -> (shard, DeviceIndex|None,
        # PlaneDeviceIndex|None) — ONE atomic triple per key: a search
        # must never pair a shard snapshot with a plane index from a
        # different (re-)ingestion, so they live in the same value
        self._indexes: dict[
            tuple[str, str], tuple[VariantIndexShard, object, object]
        ] = {}
        eng = self.config.engine
        if eng.microbatch:
            from .serving import MicroBatcher

            res = getattr(self.config, "resilience", None)
            self._batcher = MicroBatcher(
                max_batch=eng.microbatch_max,
                max_wait_ms=eng.microbatch_wait_ms,
                default_timeout_s=getattr(res, "batch_timeout_s", None),
                pipeline_depth=getattr(eng, "fetch_pipeline_depth", 2),
            )
        else:
            self._batcher = None
        # response cache (response_cache.py): serves repeated queries
        # from host memory with zero device launches; keys embed
        # index_fingerprint() and publishes invalidate, so a stale
        # answer is structurally unreachable
        if getattr(eng, "response_cache", True) and (
            getattr(eng, "response_cache_size", 4096) > 0
        ):
            self._response_cache = ResponseCache(
                max_entries=eng.response_cache_size,
                ttl_s=getattr(eng, "response_cache_ttl_s", 300.0),
            )
        else:
            self._response_cache = None
        # guards the dispatch counters (fused_searches, mesh_searches)
        self._mat_lock = threading.Lock()
        # persistent per-dataset scatter pool (serving hot path: no
        # per-request thread churn)
        self._scatter = ThreadPoolExecutor(
            max_workers=32, thread_name_prefix="engine-scatter"
        )
        # mesh serving state (parallel/mesh.py StackedIndex + sharded
        # arrays), rebuilt lazily after (re-)ingestion; None when <2
        # devices are visible or use_mesh is off. mesh_searches counts
        # queries answered by the one-pjit-program path (observability +
        # the multichip dryrun asserts it engaged).
        self._mesh_lock = threading.Lock()
        self._mesh_state = None
        self._mesh_dirty = True
        # fused cross-shard dispatch state (ops.kernel.FusedDeviceIndex
        # over every warm XLA-kernel shard), rebuilt lazily after
        # (re-)ingestion like the mesh stack; fused_searches counts
        # multi-dataset queries answered by ONE fused launch
        self._fused_state = None
        self._fused_dirty = True
        # publish generation: a finished build only publishes if no
        # _publish_index happened since its inputs were snapshotted —
        # the dirty flag alone cannot tell WHICH claim a slow build
        # belongs to (two racing builds could publish out of order)
        self._fused_gen = 0
        self.fused_searches = 0
        # how requests' targets got their responses: ``skipped`` from
        # the launch's own counts (it matched nothing there), ``inline``
        # materialised on the request's thread, ``pooled`` by a task of
        # the scatter pool (engine.pool_wait times each task's wait for
        # a thread); the three add up to the targets asked
        self.materialized = {"skipped": 0, "inline": 0, "pooled": 0}
        # samples requests selected, summed over their datasets
        self.selected_samples = 0
        self.mesh_searches = 0
        # multi-dataset requests on a host with more than one device
        # whose BASE targets did not all take the mesh stack, by reason
        # (_note_mesh_skip); _mesh_why says why the state reads None
        self.mesh_skips: dict[str, int] = {}
        self._mesh_why = "unbuilt"
        # warm phases that failed and were skipped since the last
        # warmup() run started (see _warm_failed)
        self.warmup_failed_phases = 0
        # set by the first warmup(): from then on the engine is serving,
        # and whatever it publishes (a base index, the fused stack, the
        # mesh stack) compiles its programs BEFORE it becomes routable —
        # the way the L0 tier always has. Before that (load_all at
        # start-up) publishes are plain and warmup() compiles them all.
        self._keep_warm = False
        # token -> (owner chip, bytes) reserved for an in-flight plane
        # upload: counts against the OWNER's plane_hbm_budget_gb until
        # the planes are published.
        self._plane_reserved: dict = {}
        # placement: key -> [owner device, bytes placed there]. Every
        # published key has ONE owner among jax.local_devices(): its
        # tiles and its planes are resident there and its programs run
        # there. A key keeps its owner across republishes (a re-ingest
        # or a fold lands where the old copy lived); a dropped dataset
        # gives its chip back. Guarded by _mesh_lock.
        self._placement: dict[tuple[str, str], list] = {}
        self._assignments: dict[str, int] = {}
        # keys whose planes should be on their owner and are not (the
        # owner's budget declined them, or the upload failed): a
        # request that reads such a key's planes is served from the
        # host's copy and COUNTED (device.fallbacks{host_planes})
        self._planes_declined: set = set()
        # launch groups of the plane readers: key -> (group, slot). A
        # group is the tuple of (key, dindex, planes) of plane datasets
        # that lie on ONE chip and are alike in tile size, mask width
        # and count planes, in the order they were first published, at
        # most SELECTED_SLOTS of them; the targets of one request that
        # fall into one group ride ONE match+planes launch
        # (_search_targets). Rebuilt copy-on-write by whoever publishes
        # or drops a base index (_regroup_planes), its programs
        # compiled there before the map is published when serving.
        # _plane_gen counts the changes to what the map is built from
        # (a base publish, a drop, planes leaving before a re-ingest):
        # a map built from older inputs is never published.
        self._plane_groups: dict = {}
        self._plane_gen = 0
        # last computed HBM-ledger snapshot: /device/status reads it
        # when the publish lock is busy (a rebuild can hold _mesh_lock
        # for seconds, and a status probe must answer anyway)
        self._plane_ledger_cache: dict = {
            "residentBytes": 0,
            "reservedBytes": 0,
            "reservedTokens": 0,
            "fullestChipBytes": 0,
        }
        # wall time the current fused stack was published (stack age
        # on the /device/status stacks surface)
        self._fused_built_at: float | None = None
        # cached index-set identity, recomputed under _mesh_lock at
        # every publish: the query hot path (cache keys, async-job
        # fingerprints) reads it per request, so it must be O(1) and
        # never iterate _indexes concurrently with an ingest
        self._fingerprint = ""
        # ingest-while-serving delta tail: base_key -> {epoch: shard}.
        # A delta is just another (dataset, vcf)-keyed shard — small,
        # host-served (no device index), tagged with its coordinate
        # envelope and a per-key epoch. Deltas publish WITHOUT touching
        # the mesh/fused dirty flags or the base fingerprint, so the
        # warm base stacks keep serving across a publish; a base
        # publish (compaction / re-ingest) atomically drops the folded
        # epochs. All three fingerprint views and the serving list are
        # rebuilt copy-on-write under _mesh_lock so the query hot path
        # never iterates a dict an ingest is mutating.
        self._deltas: dict[tuple[str, str], dict[int, object]] = {}
        self._delta_seq: dict[tuple[str, str], int] = {}
        # L0 delta-tail mini-index (ISSUE 15): keys whose tail passed
        # the depth/row threshold get their shards stacked into ONE
        # secondary fused device index (ops.kernel.L0DeviceIndex),
        # published copy-on-write next to the base stacks. A search
        # then splits targets THREE ways — mesh/fused base stack, L0
        # stack (one batched launch for all covered tail rows across
        # keys), host scan for the sub-threshold residue. A base
        # publish retires the covered coverage in the same critical
        # section that drops the delta epochs, so rows are never
        # doubled or missing. State tuple:
        # (findex, {serve_key: sid}, {serve_key: shard}, rows, built_at)
        self._l0_state: tuple | None = None
        # per-(dataset, vcf) L0 blocks (ISSUE 20): each covered key
        # keeps its own standing L0DeviceIndex, rebuilt ONLY when that
        # key's tail changes; the published _l0_state composite
        # (ops.kernel.CompositeL0DeviceIndex) assembles the standing
        # blocks device-side, so a publish to key A never re-stacks
        # key B's columns. Copy-on-write under _mesh_lock like the
        # delta registry. Value: (block_findex, [(serve_key, shard),
        # ...], built_at).
        self._l0_blocks: dict[tuple[str, str], tuple] = {}
        # publish generation for L0 builds (same role as _fused_gen):
        # a build whose inputs predate ANY delta/base publish must not
        # publish over fresher state
        self._l0_gen = 0
        # per-key L0 generations: a publish to key B racing a rebuild
        # bumps ONLY B's generation, so the rebuild still adopts the
        # fresh per-key blocks whose inputs did not move (their stack
        # work is never thrown away with the raced composite)
        self._l0_key_gens: dict[tuple[str, str], int] = {}
        # per-key L0 block build counts (label-capped telemetry; a
        # publish burst on one key must leave the other keys' counts
        # unmoved: tests/test_delta_ingest.py)
        self._l0_key_builds: dict[str, int] = {}
        self.l0_block_reuses = 0
        # L0 program shapes already warmed: the shard-tier/row padding
        # keeps successive builds on one shape, so warmup runs once
        # per shape — and covers the FULL batch-tier ladder (incl. the
        # big coalescing tiers), not just the common small ones
        self._l0_warmed: set = set()
        self.l0_builds = 0
        self.l0_searches = 0
        self._base_fingerprint = ""
        self._ds_fingerprints: dict[str, str] = {}
        self._ds_full_fingerprints: dict[str, str] = {}
        self._serve_list: list = []
        self.delta_publishes = 0

    # -- index management ---------------------------------------------------

    def _place(self, key, shard, at=None):
        """The owner chip of ``key``: the one it already has, else
        ``at`` (where a prebuilt index already lies), else the local
        device with the fewest bytes placed on it (ties: the lowest
        device id), by what each key holds on its owner (32 B a row of
        tiles and, when it has them, its planes). Nothing to configure:
        the owner follows from the devices JAX reports and the bytes of
        what is published, and start-up publishes in one fixed order,
        so a restart places alike. On one device the owner is that
        device."""
        import jax

        from .ops.plane_kernel import PlaneDeviceIndex, chip_of

        want = shard.n_rows * 32 + (
            PlaneDeviceIndex.estimate_hbm(shard)
            if getattr(self.config.engine, "device_planes", True)
            else 0
        )
        with self._mesh_lock:
            placed = self._placement.get(key)
            if placed is not None:
                placed[1] = int(want)
                return placed[0]
            devices = jax.local_devices()
            load = {d: 0 for d in devices}
            for dev, nbytes in self._placement.values():
                load[dev] = load.get(dev, 0) + nbytes
            owner = at or min(devices, key=lambda d: (load[d], d.id))
            self._placement[key] = [owner, int(want)]
            chip = str(chip_of(owner))
            self._assignments[chip] = self._assignments.get(chip, 0) + 1
            return owner

    def _build_planes(self, key, shard, dindex, owner):
        """Device-resident genotype planes for the selected-samples leaf
        (ops/plane_kernel.py) on ``owner``, gated on that chip's HBM
        budget — oversized plane sets stay host-resident, and a request
        that then reads them from the host is a counted fall-back
        (``_planes_declined``)."""
        eng = self.config.engine
        self._planes_declined.discard(key)
        if (
            dindex is None
            or shard.gt_bits is None
            or not getattr(eng, "device_planes", True)
        ):
            return None
        from .ops.plane_kernel import PlaneDeviceIndex

        budget = getattr(eng, "plane_hbm_budget_gb", 11.0) * 1e9
        est = PlaneDeviceIndex.estimate_hbm(shard)
        # PER-CHIP gate: the planes resident ON THE OWNER and the
        # in-flight uploads to it (reservations) count against the
        # budget, not their sum over the process — reserve under the
        # lock BEFORE uploading so two concurrent add_index calls
        # cannot both pass the gate and jointly exceed it.
        # Re-ingestion republishes the key plane-less first so searches
        # in that window take the host fallback (the old PlaneDeviceIndex
        # may still be referenced by an in-flight search or a mesh stack,
        # so its HBM is only truly freed when those drop it — the budget
        # is a watermark, not a hard cap, across that window).
        token = object()  # unique per upload: same-key races each hold one
        with self._mesh_lock:
            prior = self._indexes.get(key)
            if prior is not None and prior[2] is not None:
                self._indexes[key] = (prior[0], prior[1], None)
                self._rebuild_serving_state_locked()
                self._ungroup_locked(key)
            prior = None  # noqa: F841
            # the owner's resident planes (the same key's were just
            # republished plane-less above, so every remaining p counts)
            # + EVERY in-flight reservation on it, including concurrent
            # uploads of this same key — each holds its own token
            used = self._plane_hbm_resident_locked(owner)
            over = used + est > budget
            if over:
                self._planes_declined.add(key)
            else:
                self._plane_reserved[token] = (owner, est)
        if over:
            logging.getLogger(__name__).info(
                "genotype planes for %s exceed the HBM budget of their "
                "owner chip (%.1f GB resident+reserved there); "
                "host-resident",
                key,
                used / 1e9,
            )
            return None
        try:
            # reservation is released when the caller PUBLISHES the
            # planes to _indexes (at which point they count as resident)
            # or here on failure — never while the upload is in neither
            # ledger. The token rides on the object so the publisher
            # releases exactly this upload's reservation.
            # every upload writes its chunks into the resident padded
            # array in place (staged_device_put): beside the bytes the
            # gate reserved it holds only the chunks in flight, so a
            # monolithic upload (chunk_mb <= 0) is the one that holds
            # more, and nothing falls back to it
            chunk_mb = getattr(eng, "plane_upload_chunk_mb", 256)
            chunk_bytes = (
                chunk_mb * 1024 * 1024 if chunk_mb > 0 else None
            )
            fault_point("device.bringup", "plane_upload")
            planes = PlaneDeviceIndex(
                shard, upload_chunk_bytes=chunk_bytes, device=owner
            )
            planes._hbm_reservation = token
            return planes
        except Exception:
            _device_fallback(
                "plane_upload",
                "plane upload failed for %s; host-resident",
                key,
            )
            with self._mesh_lock:
                self._plane_reserved.pop(token, None)
                self._planes_declined.add(key)
            return None

    def add_index(self, shard: VariantIndexShard) -> None:
        key = (shard.meta.get("dataset_id", ""), shard.meta.get("vcf_location", ""))
        owner = self._place(key, shard)
        try:
            fault_point("device.bringup", "index_build")
            dindex = make_device_index(
                shard, window=self.config.engine.window_cap, device=owner
            )
        except Exception:
            # accelerator unavailable (backend init failure, OOM): serve
            # from the host matcher instead of failing ingestion/queries —
            # query serving must not depend on one specific compute
            # resource. Full traceback is logged so programming errors in
            # DeviceIndex are not silently downgraded.
            _device_fallback(
                "index_build",
                "device index unavailable for %s; serving host-only",
                key,
            )
            dindex = None
        planes = self._build_planes(key, shard, dindex, owner)
        # the name -> position map a filtered request reads, built here
        # on the publishing thread
        shard.sample_positions()
        shard.sample_name_array()
        if self._keep_warm:
            # a serving engine: the new index's programs compile HERE,
            # on the publishing thread (ingest, compaction, /reload),
            # so neither a request nor the canary's first probe of the
            # dataset pays for them
            self._warm_index(shard, dindex, planes)
        # publish + dirty-mark in one critical section: a concurrent
        # search must never pair the new shard with a mesh stack built
        # from the old one (_mesh_ready reads _indexes under this lock)
        self._publish_index(key, shard, dindex, planes)
        if self._keep_warm:
            # ... and the stacks the publish dirtied are rebuilt (and,
            # serving, warmed before they publish) now rather than by
            # the first multi-dataset request; per-shard dispatch
            # serves meanwhile
            self.rebuild_stacks()

    def _publish_index(self, key, shard, dindex, planes) -> None:
        """Publish the (shard, dindex, planes) triple + dirty-mark + HBM
        reservation release in ONE critical section: a concurrent search
        must never pair the new shard with a stale mesh stack, and the
        reservation must convert to residency atomically (never counted
        twice, never counted nowhere).

        This is the BASE publish seam (initial ingest, re-ingest, and
        the compactor's fold): it bumps the base fingerprint, dirties
        the fused/mesh stacks, and atomically drops the delta epochs
        the published shard folded (``meta['delta_epoch']`` = highest
        folded epoch; absent means wholesale replacement — every delta
        for the key dies with it). Cache invalidation is scoped to the
        published dataset — entries touching only other datasets keep
        serving (their keys embed per-dataset components that did not
        change)."""
        with self._mesh_lock:
            self._mesh_dirty = True
            self._fused_dirty = True
            self._fused_gen += 1
            self._plane_gen += 1
            self._indexes[key] = (shard, dindex, planes)
            # epoch monotonicity survives restarts: a reloaded base
            # carries the highest epoch it folded, and new deltas must
            # number PAST it or a stale on-disk artifact could
            # masquerade as covering them
            baked = shard.meta.get("delta_epoch") or 0
            if baked > self._delta_seq.get(key, 0):
                self._delta_seq[key] = baked
            tail = self._deltas.get(key)
            if tail:
                folded = shard.meta.get("delta_epoch")
                kept = (
                    {}
                    if folded is None
                    else {e: s for e, s in tail.items() if e > folded}
                )
                deltas = dict(self._deltas)
                if kept:
                    deltas[key] = kept
                else:
                    deltas.pop(key, None)
                self._deltas = deltas
            # the covered L0 generation dies in the SAME critical
            # section that drops the folded epochs: the serve list and
            # the L0 coverage map change together, so a query can
            # never pair the new base with tail rows the fold already
            # absorbed (doubled) or find neither (missing)
            self._l0_touch_key_locked(key)
            self._retire_l0_key_locked(key)
            self._rebuild_serving_state_locked()
            self._plane_reserved.pop(
                getattr(planes, "_hbm_reservation", None), None
            )
        # the per-dataset fingerprint component in every cache key
        # already makes this dataset's old entries unreachable; the
        # scoped invalidation frees them now WITHOUT dropping other
        # datasets' warm entries (wholesale clear when the knob is off)
        self._invalidate_cache(key[0], None)
        # the chip's launch group now holds this key's new buffers:
        # requests launch it alone (its own programs are warm) until
        # the group's programs are compiled, here
        self._regroup_planes()

    def _regroup_planes(self) -> int:
        """Rebuild the plane readers' launch groups (``_plane_groups``)
        from what is published, on the publishing thread; returns the
        programs compiled. A group whose members are the very objects
        of a standing group IS that group (nothing compiles); a new
        one of two or more members has every program it can be
        launched as compiled first when the engine is serving
        (``_keep_warm``), so no request pays for a publish. A map a
        racing publish has outdated is dropped and built again. A
        request holds a group only through identity checks on its
        members, so a map that trails the published set costs launches
        (the new key rides alone), never an answer."""
        from .ops.plane_kernel import chip_of
        from .ops.scatter_kernel import SELECTED_SLOTS, ScatterDeviceIndex

        n = 0
        while True:
            with self._mesh_lock:
                gen = self._plane_gen
                first_seen = {k: i for i, k in enumerate(self._placement)}
                readers = sorted(
                    (
                        (k, d, p)
                        for k, (_s, d, p) in self._indexes.items()
                        if p is not None and isinstance(d, ScatterDeviceIndex)
                    ),
                    key=lambda m: (first_seen.get(m[0], len(first_seen)), m[0]),
                )
                standing = {
                    g for g, _slot in self._plane_groups.values()
                }
            alike: dict = {}
            for m in readers:
                _k, d, p = m
                alike.setdefault(
                    (
                        chip_of(p.device), chip_of(d.device), d.tile,
                        p.n_words, p.has_counts,
                    ),
                    [],
                ).append(m)
            groups = {}
            for members in alike.values():
                for i in range(0, len(members), SELECTED_SLOTS):
                    group = tuple(members[i : i + SELECTED_SLOTS])
                    # tuples of the same objects compare equal: the
                    # standing group is kept, with what it compiled
                    if (
                        len(group) > 1
                        and self._keep_warm
                        and group not in standing
                    ):
                        n += self._warm_group(group)
                    for slot, m in enumerate(group):
                        groups[m[0]] = (group, slot)
            with self._mesh_lock:
                if self._plane_gen == gen:
                    self._plane_groups = groups
                    return n

    def _ungroup_locked(self, key) -> None:
        """Dissolve ``key``'s launch group (held under ``_mesh_lock``):
        a group names every member's buffers, so while ANY of them is
        grouped with ``key`` its planes stay on the chip. The others
        ride alone (an index's own programs are always warm) until
        whoever took the planes out publishes again and regroups."""
        self._plane_gen += 1  # a map being built from them is dropped
        held = self._plane_groups.get(key)
        if held is not None:
            self._plane_groups = {
                k: v
                for k, v in self._plane_groups.items()
                if v[0] is not held[0]
            }

    def _warm_group(self, group) -> int:
        """Compile every program a launch group of two or more members
        can be launched as (a group of one is its index's own,
        ``_warm_index``); returns how many."""
        from .ops.scatter_kernel import warmup_selected

        eng = self.config.engine
        try:
            fault_point("device.bringup", "warmup_group")
            with device_warmup_phase():
                return warmup_selected(
                    [(d, p) for _k, d, p in group],
                    window_cap=eng.window_cap,
                    record_cap=eng.record_cap,
                )
        except Exception:
            self._warm_failed(
                "warmup_group",
                "launch group warmup failed for %s",
                [k[0] for k, _d, _p in group],
            )
            return 0

    def _invalidate_cache(self, dataset_id: str, regions) -> None:
        """Evict cache entries a publish could have answered differently:
        scoped to (dataset, per-chromosome coordinate envelope) when
        scoped invalidation is on, wholesale otherwise. ``regions`` is
        ``[(chrom, lo, hi), ...]`` or None for every region."""
        cache = self._response_cache
        if cache is None:
            return
        if not getattr(self.config.engine, "scoped_invalidation", True):
            cache.invalidate()
            return
        if regions is None:
            cache.invalidate_scope([dataset_id], None, None)
            return
        for chrom, lo, hi in regions:
            cache.invalidate_scope([dataset_id], chrom, (lo, hi))

    def _rebuild_serving_state_locked(self) -> None:
        """Recompute the serving list + all three fingerprint views
        (held under ``_mesh_lock``): the base fingerprint (base shards
        only — the staleness signal the fused/mesh stacks key on,
        STABLE across delta publishes), the
        per-dataset components (response-cache keys), and the full
        fingerprint (base + delta tail — the freshness signal async-job
        keys and worker ``/datasets`` replica grouping need). All are
        rebound as fresh objects so lock-free readers never observe a
        half-mutated structure."""
        serve: list = []
        base_parts: list[str] = []
        ds_fp: dict[str, str] = {}
        for (ds, vcf), (s, d, p) in sorted(self._indexes.items()):
            comp = (
                f"{vcf}|{s.meta.get('variant_count')}"
                f"|{s.meta.get('call_count')}|{s.n_rows}"
            )
            base_parts.append(f"{ds}|{comp}")
            ds_fp[ds] = f"{ds_fp[ds]}&{comp}" if ds in ds_fp else comp
            serve.append((ds, vcf, (s, d, p)))
        delta_parts: list[str] = []
        for (ds, vcf), tail in sorted(self._deltas.items()):
            for epoch, s in sorted(tail.items()):
                serve.append((ds, f"{vcf}#d{epoch}", (s, None, None)))
                delta_parts.append(f"{ds}|{vcf}#d{epoch}|{s.n_rows}")
        serve.sort(key=lambda t: (t[0], t[1]))
        ds_full: dict[str, str] = {}
        for (ds, vcf), (s, _d, _p) in sorted(self._indexes.items()):
            part = (
                f"{vcf}|{s.meta.get('variant_count')}"
                f"|{s.meta.get('call_count')}|{s.n_rows}"
            )
            ds_full[ds] = f"{ds_full[ds]}&{part}" if ds in ds_full else part
        for (ds, vcf), tail in sorted(self._deltas.items()):
            for epoch, s in sorted(tail.items()):
                part = f"{vcf}#d{epoch}|{s.n_rows}"
                ds_full[ds] = (
                    f"{ds_full[ds]}&{part}" if ds in ds_full else part
                )
        self._serve_list = serve
        self._base_fingerprint = "&".join(base_parts)
        self._fingerprint = self._base_fingerprint + (
            "&" + "&".join(delta_parts) if delta_parts else ""
        )
        self._ds_fingerprints = ds_fp
        self._ds_full_fingerprints = ds_full

    def add_delta(self, shard: VariantIndexShard) -> int:
        """Publish a small delta shard IMMEDIATELY (read-your-writes):
        the rows become queryable on the next search without touching
        the warm base stacks — the mesh/fused state stays clean, the
        base fingerprint is unchanged, and only cache entries whose
        dataset AND region overlap the new rows are evicted. Returns
        the assigned epoch. The caller asserts the rows are NEW (not
        already present in the key's base shard); the background
        compactor later folds the tail into the base via
        :meth:`add_index` with ``meta['delta_epoch']`` set."""
        key = (
            shard.meta.get("dataset_id", ""),
            shard.meta.get("vcf_location", ""),
        )
        regions = shard_regions(shard)
        with self._mesh_lock:
            epoch = self._delta_seq.get(key, 0) + 1
            self._delta_seq[key] = epoch
            shard.meta["delta_epoch"] = epoch
            tail = dict(self._deltas.get(key, {}))
            tail[epoch] = shard
            deltas = dict(self._deltas)
            deltas[key] = tail
            self._deltas = deltas
            self._l0_touch_key_locked(key)
            self._rebuild_serving_state_locked()
            self.delta_publishes += 1
        self._invalidate_cache(key[0], regions)
        publish_event(
            "ingest.delta_publish",
            dataset=key[0],
            vcf=key[1],
            epoch=epoch,
            rows=shard.n_rows,
        )
        # past the tail threshold the key's shards stack into the L0
        # mini-index (inline on the publishing thread — ingest-side,
        # never a request thread; a no-op below the threshold)
        self._rebuild_l0()
        return epoch

    def has_index(self, dataset_id: str, vcf_location: str) -> bool:
        """Whether a BASE shard is published for the key (the streaming
        ingest gate: re-summarising an already-served VCF must not
        stream its slices as deltas — they would duplicate base rows)."""
        return (dataset_id, vcf_location) in self._indexes

    def delta_depth(self, dataset_id: str, vcf_location: str) -> int:
        """Delta shards standing for the key (the compaction trigger)."""
        return len(self._deltas.get((dataset_id, vcf_location), ()))

    def delta_snapshot(self, key: tuple | None = None):
        """``[(key, base_shard|None, [(epoch, shard), ...]), ...]`` for
        every key with a standing delta tail, under the publish lock —
        the compactor folds from this. ``key`` scopes the snapshot to
        one ``(dataset, vcf)`` (the depth-trigger fold must touch only
        the key that tripped it, never every standing tail)."""
        with self._mesh_lock:
            out = []
            for k, tail in sorted(self._deltas.items()):
                if key is not None and k != key:
                    continue
                base = self._indexes.get(k)
                out.append(
                    (k, base[0] if base else None, sorted(tail.items()))
                )
            return out

    def replace_delta_range(self, key, epochs, shard) -> bool:
        """Atomically swap a contiguous set of standing tail ``epochs``
        for ONE merged shard — the size-tiered compactor's L1 seam
        (ISSUE 15). The merged shard takes the highest replaced epoch
        (so a later base fold retires it exactly like the raws it
        absorbed) and carries ``meta['l1_epochs'] = [lo, hi]``. The
        swap happens in one publish critical section — serve list,
        delta registry, and L0 coverage change together, so queries
        never see the range's rows doubled or missing. Returns False
        (nothing mutated) when any epoch is no longer standing — a
        racing fold or base publish won; the caller's artifact stays
        on disk for adoption by the next run."""
        epochs = sorted(int(e) for e in epochs)
        lo, hi = epochs[0], epochs[-1]
        shard.meta["dataset_id"] = key[0]
        shard.meta["vcf_location"] = key[1]
        shard.meta["delta_epoch"] = hi
        shard.meta["l1_epochs"] = [lo, hi]
        regions = shard_regions(shard)
        with self._mesh_lock:
            tail = self._deltas.get(key, {})
            if any(e not in tail for e in epochs):
                return False
            new_tail = {
                e: s for e, s in tail.items() if e not in epochs
            }
            new_tail[hi] = shard
            deltas = dict(self._deltas)
            deltas[key] = new_tail
            self._deltas = deltas
            self._l0_touch_key_locked(key)
            self._retire_l0_key_locked(key)
            self._rebuild_serving_state_locked()
        # the merged artifact serves the same ROWS the replaced deltas
        # did, but the serve-list labels changed (one '#d<hi>' entry
        # replaces the range) — evict the overlapping cached answers
        # like a delta publish would, so no stale-shaped response list
        # outlives the swap
        self._invalidate_cache(key[0], regions)
        self._rebuild_l0()
        return True

    def delta_stats(self) -> dict:
        """Per-dataset delta-tail depth for ``/debug/status``:
        ``{dataset: {"shards": n, "rows": m}}``. Lock-free over the
        copy-on-write ``_deltas`` snapshot — diagnostic surfaces must
        answer while a stack rebuild holds the publish lock."""
        deltas = self._deltas
        out: dict = {}
        for (ds, _vcf), tail in deltas.items():
            agg = out.setdefault(ds, {"shards": 0, "rows": 0})
            agg["shards"] += len(tail)
            agg["rows"] += sum(s.n_rows for s in tail.values())
        return out

    def delta_tail(self, dataset_id: str, vcf_location: str) -> dict:
        """One key's standing tail: ``{"shards": n, "rows": m}``
        (lock-free snapshot — the inline-fold ledger record reads it)."""
        tail = self._deltas.get((dataset_id, vcf_location), {})
        return {
            "shards": len(tail),
            "rows": sum(s.n_rows for s in tail.values()),
        }

    def delta_metrics(self) -> dict:
        """The ``ingest.*`` series values (register_delta_metrics);
        lock-free — /metrics scrapes must not queue behind a rebuild."""
        deltas = self._deltas
        return {
            "publishes": self.delta_publishes,
            "shards": sum(len(t) for t in deltas.values()),
            "l0_builds": self.l0_builds,
            "l0_served": self.l0_searches,
            "l0_key_builds": dict(self._l0_key_builds),
            "l0_block_reuses": self.l0_block_reuses,
        }

    # -- live shard migration (ISSUE 16) ------------------------------------

    def migration_manifest(self, dataset_id: str) -> dict:
        """The dataset's artifact inventory for the migration copy
        phase, read under the publish lock so base and tail are ONE
        consistent cut. Per-artifact identity rides the SAME
        epoch-ranged fingerprint components replica grouping reads
        (the 4-field base comp, the ``vcf#d<epoch>|rows`` tail parts):
        a crashed copy's re-run diffs manifests by these keys and
        resumes — already-adopted artifacts are skipped, never
        re-streamed."""
        with self._mesh_lock:
            artifacts: list[dict] = []
            for (ds, vcf), (s, _d, _p) in sorted(self._indexes.items()):
                if ds != dataset_id:
                    continue
                artifacts.append(
                    {
                        "kind": "base",
                        "vcf": vcf,
                        "fingerprint": (
                            f"{vcf}|{s.meta.get('variant_count')}"
                            f"|{s.meta.get('call_count')}|{s.n_rows}"
                        ),
                        "rows": int(s.n_rows),
                        "deltaEpoch": int(
                            s.meta.get("delta_epoch") or 0
                        ),
                    }
                )
            for (ds, vcf), tail in sorted(self._deltas.items()):
                if ds != dataset_id:
                    continue
                for epoch, s in sorted(tail.items()):
                    art = {
                        "kind": "delta",
                        "vcf": vcf,
                        "epoch": int(epoch),
                        "fingerprint": f"{vcf}#d{epoch}|{s.n_rows}",
                        "rows": int(s.n_rows),
                    }
                    l1 = s.meta.get("l1_epochs")
                    if l1:
                        art["l1Epochs"] = [int(l1[0]), int(l1[-1])]
                    artifacts.append(art)
        doc: dict = {"dataset": dataset_id, "artifacts": artifacts}
        # the canary bracket rides along (outside the lock — it reads
        # the copy-on-write serve list) so the migration controller's
        # verify phase probes source and target with the SAME
        # known-answer grammar the canary prober uses
        bracket = self.canary_brackets().get(dataset_id)
        if bracket:
            doc["bracket"] = bracket
        return doc

    def export_artifact(
        self, dataset_id: str, vcf: str, epoch=None
    ):
        """One serving artifact for the migration fetch — the base
        shard when ``epoch`` is None, else the standing delta at that
        epoch — or None when it no longer stands (a racing fold
        retired it; the copier re-diffs manifests and moves on).
        Lock-free: GIL-atomic dict reads over immutable triples."""
        key = (dataset_id, vcf)
        if epoch is None:
            triple = self._indexes.get(key)
            return None if triple is None else triple[0]
        return (self._deltas.get(key) or {}).get(int(epoch))

    def adopt_delta(self, shard: VariantIndexShard, epoch: int) -> bool:
        """Install a MIGRATED delta shard at its ORIGINAL epoch.
        Unlike :meth:`add_delta` — which assigns the next local epoch —
        adoption must preserve the source's numbering, or the target's
        tail fingerprint parts could never equal the source's and
        dual-serve grouping would hold the copies divergent forever.
        Idempotent for the crashed-copy resume: returns False (nothing
        mutated) when the epoch already stands or a base publish
        already folded past it."""
        epoch = int(epoch)
        key = (
            shard.meta.get("dataset_id", ""),
            shard.meta.get("vcf_location", ""),
        )
        regions = shard_regions(shard)
        with self._mesh_lock:
            base = self._indexes.get(key)
            baked = (
                base[0].meta.get("delta_epoch") or 0
            ) if base else 0
            tail = dict(self._deltas.get(key, {}))
            if epoch <= baked or epoch in tail:
                return False
            shard.meta["delta_epoch"] = epoch
            tail[epoch] = shard
            deltas = dict(self._deltas)
            deltas[key] = tail
            self._deltas = deltas
            if epoch > self._delta_seq.get(key, 0):
                self._delta_seq[key] = epoch
            self._l0_touch_key_locked(key)
            self._rebuild_serving_state_locked()
            self.delta_publishes += 1
        self._invalidate_cache(key[0], regions)
        publish_event(
            "ingest.delta_adopt",
            dataset=key[0],
            vcf=key[1],
            epoch=epoch,
            rows=shard.n_rows,
        )
        self._rebuild_l0()
        return True

    def drop_dataset(self, dataset_id: str) -> int:
        """Retire EVERY shard (base + standing tail) of one dataset in
        a single publish critical section — the migration cut-over's
        final step on the source, after the router stopped routing to
        it and its in-flight legs drained (and the rollback's cleanup
        on a half-copied target). Copy-on-write like the delta
        registry, so lock-free diagnostic readers never observe a
        half-removed dataset. Returns the base shards removed (0 =
        dataset unknown)."""
        with self._mesh_lock:
            base_keys = [
                k for k in self._indexes if k[0] == dataset_id
            ]
            delta_keys = [
                k for k in self._deltas if k[0] == dataset_id
            ]
            if not base_keys and not delta_keys:
                return 0
            if base_keys:
                indexes = dict(self._indexes)
                for k in base_keys:
                    indexes.pop(k, None)
                self._indexes = indexes
            if delta_keys:
                deltas = dict(self._deltas)
                for k in delta_keys:
                    deltas.pop(k, None)
                self._deltas = deltas
            for k in base_keys:
                self._placement.pop(k, None)
                self._planes_declined.discard(k)
            for k in set(base_keys) | set(delta_keys):
                self._delta_seq.pop(k, None)
                self._l0_touch_key_locked(k)
                self._retire_l0_key_locked(k)
            self._mesh_dirty = True
            self._fused_dirty = True
            self._fused_gen += 1
            self._plane_gen += 1
            self._rebuild_serving_state_locked()
        self._invalidate_cache(dataset_id, None)
        publish_event(
            "ingest.dataset_drop",
            dataset=dataset_id,
            shards=len(base_keys),
        )
        self._rebuild_l0()
        if base_keys:
            self._regroup_planes()
        if base_keys and self._keep_warm:
            # serving: the smaller stacks are rebuilt and warmed here,
            # on the control-plane thread, like after a publish
            self.rebuild_stacks()
        return len(base_keys)

    # -- L0 delta-tail mini-index (ISSUE 15) --------------------------------

    def _l0_covered_keys(self, deltas) -> list:
        """Keys whose standing tail is past the L0 threshold (depth in
        shards OR total rows; a 0 disables that trigger, both 0
        disables the tier)."""
        eng = self.config.engine
        min_shards = getattr(eng, "l0_min_shards", 4)
        min_rows = getattr(eng, "l0_min_rows", 4096)
        if min_shards <= 0 and min_rows <= 0:
            return []
        out = []
        for key, tail in sorted(deltas.items()):
            if min_shards > 0 and len(tail) >= min_shards:
                out.append(key)
                continue
            if min_rows > 0 and (
                sum(s.n_rows for s in tail.values()) >= min_rows
            ):
                out.append(key)
        return out

    def _l0_touch_key_locked(self, key) -> None:
        """Record that ``key``'s tail moved (held under ``_mesh_lock``):
        bumps the global L0 generation (a racing composite publish must
        lose) AND the key's own generation, so a rebuild racing a
        publish to a DIFFERENT key still adopts the per-key blocks
        whose inputs did not move — only the raced composite is
        discarded, never the untouched keys' stack work."""
        self._l0_gen += 1
        self._l0_key_gens[key] = self._l0_key_gens.get(key, 0) + 1

    def _retire_l0_key_locked(self, key) -> None:
        """Drop one key's entries from the L0 coverage map (held under
        ``_mesh_lock``): its epochs were folded into a base, replaced
        by an L1 artifact, or wholesale-republished. The stacked
        arrays may keep dead rows until the next build — harmless,
        nothing routes to them — but coverage and the serve list must
        change in the same critical section."""
        if key in self._l0_blocks:
            # the standing per-key block covered epochs that no longer
            # serve; drop it copy-on-write so the next rebuild restacks
            # this key (and ONLY this key) from the live tail
            blocks = dict(self._l0_blocks)
            blocks.pop(key, None)
            self._l0_blocks = blocks
        state = self._l0_state
        if state is None:
            return
        ds, vcf = key
        prefix = f"{vcf}#d"
        findex, sid_of, shard_of, rows, built_at = state
        kept = {
            k: sid
            for k, sid in sid_of.items()
            if not (k[0] == ds and k[1].startswith(prefix))
        }
        if len(kept) == len(sid_of):
            return
        if not kept:
            self._l0_state = None
        else:
            self._l0_state = (
                findex,
                kept,
                {k: shard_of[k] for k in kept},
                rows,
                built_at,
            )

    def _rebuild_l0(self) -> None:
        """Stack every past-threshold tail into a fresh L0 mini-index
        and publish it copy-on-write (generation-checked, like the
        fused stack build: a delta/base publish racing the build wins
        and the next trigger rebuilds). Runs on the PUBLISHING thread
        — delta publication is ingest-side, never a request thread —
        and pre-warms the batch-tier programs inside a warmup phase so
        the first request launch is a compile-cache hit.

        Per-key slicing (ISSUE 20): the stack is sharded by
        (dataset, vcf) — each covered key keeps a standing
        :class:`~.ops.kernel.L0DeviceIndex` block, and a publish to
        key A restacks ONLY key A's block; the published index is a
        :class:`~.ops.kernel.CompositeL0DeviceIndex` assembling the
        standing blocks with a cheap device-side concat. Build work is
        therefore proportional to the TOUCHED key's tail, not the sum
        of all covered tails."""
        with self._mesh_lock:
            gen = self._l0_gen
            key_gens = dict(self._l0_key_gens)
            deltas = self._deltas
            blocks = self._l0_blocks
        keys = self._l0_covered_keys(deltas)
        if not keys:
            with self._mesh_lock:
                if self._l0_gen == gen:
                    self._l0_state = None
                    self._l0_blocks = {}
            return
        # resolve each covered key to a standing block (reused when
        # the key's entry list is identity-equal) or a fresh stack
        fresh: dict = {}  # key -> (block, entries, built_at)
        per_key: dict = {}
        reused = 0
        for key in keys:
            ds, vcf = key
            entries = [
                ((ds, f"{vcf}#d{epoch}"), shard)
                for epoch, shard in sorted(deltas[key].items())
            ]
            standing = blocks.get(key)
            if standing is not None:
                _b, old_entries, _t = standing
                if len(old_entries) == len(entries) and all(
                    a[0] == b[0] and a[1] is b[1]
                    for a, b in zip(old_entries, entries)
                ):
                    per_key[key] = standing
                    reused += 1
                    continue
            try:
                from .ops.kernel import L0DeviceIndex

                block = L0DeviceIndex([s for _k, s in entries])
            except Exception:
                _device_fallback(
                    "l0_build", "L0 block build failed; the tail host-scans"
                )
                return
            standing = (block, entries, time.time())
            per_key[key] = standing
            fresh[key] = standing
        state = self._l0_state
        if not fresh and state is not None:
            all_entries = [
                e for key in keys for e in per_key[key][1]
            ]
            sid_of, shard_of = state[1], state[2]
            if len(sid_of) == len(all_entries) and all(
                shard_of.get(k) is s for k, s in all_entries
            ):
                # coverage identical (e.g. a sub-threshold key
                # published) AND every block standing: nothing to
                # stack, nothing to compose
                return
        try:
            from .ops.kernel import CompositeL0DeviceIndex

            findex = CompositeL0DeviceIndex(
                [per_key[k][0] for k in keys]
            )
        except Exception:
            _device_fallback(
                "l0_build",
                "L0 composite assembly failed; the tail host-scans",
            )
            return
        sid_of = {}
        shard_of = {}
        for key, off in zip(keys, findex.block_sid_offsets):
            for j, (serve_key, shard) in enumerate(per_key[key][1]):
                sid_of[serve_key] = off + j
                shard_of[serve_key] = shard
        # warm BEFORE publishing: a request arriving between publish
        # and warm would dispatch a novel (program, shape) uncompiled
        # — a mid-request XLA compile on the serving path, the exact
        # regression this tier exists to avoid. Warming an unpublished
        # index is safe (same process-wide compile cache), and a
        # race-discarded build merely pre-warmed shapes the next
        # build reuses.
        self._l0_warm(findex)
        state = (
            findex,
            sid_of,
            shard_of,
            int(findex.n_rows),
            time.time(),
        )
        with self._mesh_lock:
            # adopt fresh blocks whose OWN key did not move — a publish
            # to key B racing this build must not discard key A's stack
            # work (the composite below may still lose on the global
            # generation; the adopted blocks make the NEXT build cheap)
            adoptable = {
                k: v
                for k, v in fresh.items()
                if self._l0_key_gens.get(k, 0) == key_gens.get(k, 0)
            }
            if adoptable:
                nb = dict(self._l0_blocks)
                nb.update(adoptable)
                self._l0_blocks = nb
                for k in adoptable:
                    self._l0_count_key_build_locked(k)
            if self._l0_gen != gen:
                return  # a publish raced the build; rebuilt on the
                # next trigger against the fresher tail
            self._l0_state = state
            self.l0_builds += 1
            self.l0_block_reuses += reused
        publish_event(
            "ingest.l0_build",
            keys=len(keys),
            shards=len(sid_of),
            rows=int(findex.n_rows),
            rebuilt=len(fresh),
            reused=reused,
        )

    def _l0_count_key_build_locked(self, key) -> None:
        """Attribute one block stack to its ``dataset/vcf`` label,
        bounding the label set at the registry's cardinality cap (the
        fn-backed ``ingest.l0_key_builds`` series is guard-exempt, so
        the producer owns the bound: past the cap, new keys collapse
        into the overflow sentinel)."""
        label = f"{key[0]}/{key[1]}"
        builds = self._l0_key_builds
        if label not in builds and (
            len(builds) >= DEFAULT_MAX_LABEL_VALUES
        ):
            label = OVERFLOW_LABEL
        builds[label] = builds.get(label, 0) + 1

    def _l0_warm(self, findex) -> None:
        """Compile the L0 program at EVERY batch tier of the index's
        ladder — including the big tiers cross-request coalescing can
        reach — off the request path, ONCE per program shape (the
        shard-tier/row padding keeps successive tail builds on one
        shape, so repeat builds skip this outright instead of paying
        per-build probe launches). Inside a warmup phase: the compile
        tracker stamps these shapes expected instead of
        mid-request."""
        eng = self.config.engine
        win = min(
            eng.window_cap,
            getattr(findex, "window_hint", eng.window_cap),
        )
        shape = (
            # the class name is part of run_queries' program identity,
            # so a composite and a monolithic index at the same padded
            # dims are DIFFERENT programs — key the warm set the same
            # way or the second one skips its warm and compiles
            # mid-request
            type(findex).__name__,
            findex.n_padded,
            getattr(findex, "n_shards_padded", findex.n_shards),
            win,
            eng.record_cap,
        )
        if shape in self._l0_warmed:
            return
        try:
            with device_warmup_phase():
                for t in getattr(findex, "batch_tiers", (8, 64)):
                    run_queries_auto(
                        findex,
                        encode_queries(
                            [QuerySpec("1", 1, 1, 1, 2)] * t,
                            shard_ids=[0] * t,
                        ),
                        window_cap=win,
                        record_cap=eng.record_cap,
                    )
            self._l0_warmed.add(shape)
        except Exception:
            _device_fallback("l0_warmup", "L0 warmup failed")

    def l0_status(self) -> dict:
        """The L0 tier's state, lock-free (GIL-atomic reference read)
        — the ``/debug/status`` ingest section reads it."""
        state = self._l0_state
        doc: dict = {
            "built": state is not None,
            "builds": self.l0_builds,
            "servedQueries": self.l0_searches,
        }
        if state is not None:
            doc["shards"] = len(state[1])
            doc["rows"] = state[3]
            doc["ageS"] = round(time.time() - state[4], 1)
        # per-key block detail (ISSUE 20): an untouched key's build
        # count must not move when another key is restacked;
        # blockReuses is the complementary signal
        blocks = self._l0_blocks
        if blocks:
            doc["keys"] = {
                f"{ds}/{vcf}": {
                    "shards": len(entries),
                    "rows": int(getattr(b, "n_rows", 0)),
                    "builds": self._l0_key_builds.get(
                        f"{ds}/{vcf}", 0
                    ),
                }
                for (ds, vcf), (b, entries, _t) in sorted(
                    blocks.items()
                )
            }
        doc["blockReuses"] = self.l0_block_reuses
        return doc

    def l0_pre_rows(self, tail_targets, spec_base, payload) -> dict:
        """``{serve_key: shard-local row ids | None}`` for the
        delta-tail targets the standing L0 mini-index covers — ONE
        batched device launch answers ALL covered tail rows across
        keys, riding the micro-batcher's accumulators so concurrent
        requests coalesce into the same launch (and the launch's
        device time pro-rates onto each request's cost vector via the
        usual fetch-stage accounting). A ``None`` value marks
        window/record overflow: the caller host-scans that shard
        uncapped, the per-shard kernel contract.

        THE cost-attribution owner for the tail (ISSUE 15 satellite):
        exactly the targets about to be HOST-walked — absent from the
        returned dict (sub-threshold residue, racing republishes via
        the shard-identity check, host-only wildcard-ref semantics) or
        marked ``None`` (overflow) — charge ``delta_shards`` here, on
        the calling request's ambient context.

        ``tail_targets`` is ``[((dataset, vcf_label), shard), ...]``
        with the serve-list ``vcf#d<epoch>`` labels."""
        out = self._l0_pre_rows(tail_targets, spec_base, payload)
        n_host = sum(
            1 for key, _s in tail_targets if out.get(key) is None
        )
        if n_host:
            charge_cost(delta_shards=n_host)
        return out

    def _l0_pre_rows(self, tail_targets, spec_base, payload) -> dict:
        state = self._l0_state
        if state is None or not tail_targets:
            return {}
        if payload.selected_samples_only and not self._device_ref_ok(
            payload, spec_base
        ):
            return {}  # N-wildcard ref: host regex semantics only
        findex, sid_of, shard_of = state[0], state[1], state[2]
        routes = []
        for key, shard in tail_targets:
            sid = sid_of.get(key)
            if sid is not None and shard_of[key] is shard:
                routes.append((key, sid))
        if not routes:
            return {}
        eng = self.config.engine
        specs = [spec_base] * len(routes)
        sids = [sid for _k, sid in routes]
        # tail-sized candidate window (the index's own hint): a tail
        # shard's hit range can never exceed its row count, so the
        # tighter window is exact — it only shrinks the per-lane
        # gather. The engine-wide cap still bounds it, and a window
        # overflow keeps the host-fallback contract either way.
        win = min(
            eng.window_cap,
            getattr(findex, "window_hint", eng.window_cap),
        )
        if self._batcher is not None:
            res = self._batcher.submit_many(
                findex,
                specs,
                shard_ids=sids,
                window_cap=win,
                record_cap=eng.record_cap,
            )
        else:
            fault_point("kernel.launch")
            res = run_queries_auto(
                findex,
                encode_queries(specs, shard_ids=sids),
                window_cap=win,
                record_cap=eng.record_cap,
            )
        out = _launched_rows(findex, routes, res, eng.record_cap)
        with self._mat_lock:  # unlocked += drops concurrent counts
            self.l0_searches += 1
        annotate(dispatch_l0=len(routes))
        return out

    _AUTO_PLANES = object()  # sentinel: build planes unless caller chose

    def add_prebuilt_index(
        self, shard: VariantIndexShard, dindex, planes=_AUTO_PLANES
    ) -> None:
        """Register a shard with an ALREADY-BUILT device index (benchmarks
        and bulk loaders that construct/upload the index out of band) —
        keeps the private ``_indexes`` key/locking contract in one place.
        ``planes`` may be an out-of-band PlaneDeviceIndex or an explicit
        None (no plane upload even if the budget allows — e.g. the
        caller already tried and failed); omitted means auto-build."""
        key = (shard.meta.get("dataset_id", ""), shard.meta.get("vcf_location", ""))
        if planes is VariantEngine._AUTO_PLANES:
            # the planes go where the prebuilt index's tiles lie
            import jax

            owner = self._place(
                key,
                shard,
                at=getattr(dindex, "device", None)
                or jax.local_devices()[0],
            )
            planes = self._build_planes(key, shard, dindex, owner)
        self._publish_index(key, shard, dindex, planes)

    def rebuild_stacks(self) -> None:
        """Rebuild the fused + mesh serving stacks INLINE. The
        background compactor calls this right after a fold so the
        first post-compaction query finds warm state instead of paying
        the build (or serving per-shard while a background build
        runs). Best-effort: a failed build leaves the per-shard paths
        serving exactly as the lazy rebuild would."""
        try:
            self._fused_ready(wait=True)
        except Exception:
            _device_fallback(
                "stack_rebuild", "post-compaction fused rebuild failed"
            )
        try:
            self._mesh_ready()
        except Exception:
            _device_fallback(
                "stack_rebuild", "post-compaction mesh rebuild failed"
            )

    def warmup(self) -> int:
        """Pre-compile every kernel program serving can dispatch against
        the currently loaded indexes (tiers x exact split x batch
        shapes x fused-planes) so no request ever pays a first-compile
        (1-2 s per novel signature; VERDICT r4 next #7).
        Returns the number of programs touched. Call at server start,
        once the persisted shards are pinned; cached signatures make
        repeats near-free. From its first run on, the engine keeps
        itself warm: every later base publish compiles its own programs
        before it becomes routable (``add_index``), and so do the fused
        and mesh stacks rebuilt after it.

        Runs inside a flight-recorder warmup phase (ISSUE 14): the
        compile tracker stamps these (program, shape) keys as EXPECTED,
        so only a shape first compiled outside warmup ticks
        ``device.mid_request_compiles``.

        The batch-tier ladder is traffic-fit FIRST (ISSUE 17): the
        recorder's per-(family, tier) padding histogram may split a
        wasteful rung, and fitting before the warm loops means every
        fitted rung is pre-compiled in this same phase — the ladder
        can never grow a rung that serving would compile mid-request."""
        from .ops.kernel import refit_active_ladder

        with device_warmup_phase():
            refit_active_ladder()
            n = self._warmup()
        self._keep_warm = True
        return n

    def _warmup(self) -> int:
        # phases of THIS run that failed and were skipped (each also
        # ticks device.fallbacks{site=warmup_*}): a server that warmed
        # nothing still starts, but its caller can see it
        self.warmup_failed_phases = 0
        with self._mesh_lock:
            snapshot = list(self._indexes.values())
        # a program is compiled for the chip its operands live on: one
        # thread per owner, so four chips' programs compile side by side
        by_owner: dict = {}
        for triple in snapshot:
            by_owner.setdefault(
                getattr(triple[1], "device", None), []
            ).append(triple)

        def warm_owner(triples) -> int:
            return sum(self._warm_index(*t) for t in triples)

        if len(by_owner) > 1:
            with ThreadPoolExecutor(
                max_workers=len(by_owner), thread_name_prefix="warm-owner"
            ) as pool:
                n = sum(pool.map(warm_owner, by_owner.values()))
        else:
            n = warm_owner(snapshot)
        # ... and the launch groups the plane readers of one chip form
        for group in {g for g, _slot in self._plane_groups.values()}:
            if len(group) > 1:
                n += self._warm_group(group)
        fst = self._fused_ready(wait=True)
        if fst is not None:
            n += self._warm_fused(fst[0])
        state = self._mesh_ready()
        if state is not None:
            n += self._warm_mesh(state)
        return n

    def _warm_failed(self, site: str, msg: str, *args) -> None:
        with self._mat_lock:  # owners warm on threads of their own
            self.warmup_failed_phases += 1
        _device_fallback(site, msg, *args)

    def _warm_index(self, shard, dindex, planes) -> int:
        """Compile one index's per-shard programs; returns how many."""
        from .ops.scatter_kernel import ScatterDeviceIndex, warmup_index

        eng = self.config.engine
        n = 0
        if isinstance(dindex, ScatterDeviceIndex):
            try:
                fault_point("device.bringup", "warmup_scatter")
                with device_warmup_phase():
                    n += warmup_index(
                        dindex,
                        planes,
                        window_cap=eng.window_cap,
                        record_cap=eng.record_cap,
                    )
            except Exception:
                self._warm_failed(
                    "warmup_scatter",
                    "kernel warmup failed for %s",
                    shard.meta.get("dataset_id"),
                )
        elif dindex is not None:
            # XLA gather kernel (CPU fallback): compile every
            # batch-tier rung run_queries pads to (the process
            # ladder — the same single source run_queries reads)
            from .ops.kernel import active_ladder

            try:
                fault_point("device.bringup", "warmup_xla")
                with device_warmup_phase():
                    for t in active_ladder().rungs:
                        run_queries_auto(
                            dindex,
                            [QuerySpec("1", 1, 1, 1, 2)] * t,
                            window_cap=eng.window_cap,
                            record_cap=eng.record_cap,
                        )
                        n += 1
            except Exception:
                self._warm_failed("warmup_xla", "warmup failed")
        return n

    def _warm_fused(self, findex) -> int:
        """Fused stacked-index programs: every batch tier the serving
        batcher can emit against the cross-shard index (its 2D segment
        table makes these DISTINCT compiled signatures from the
        per-shard programs)."""
        from .ops.kernel import active_ladder

        eng = self.config.engine
        n = 0
        try:
            fault_point("device.bringup", "warmup_fused")
            with device_warmup_phase():
                for t in active_ladder().rungs:
                    run_queries_auto(
                        findex,
                        encode_queries(
                            [QuerySpec("1", 1, 1, 1, 2)] * t,
                            shard_ids=[0] * t,
                        ),
                        window_cap=eng.window_cap,
                        record_cap=eng.record_cap,
                    )
                    n += 1
        except Exception:
            self._warm_failed("warmup_fused", "fused warmup failed")
        return n

    def _warm_mesh(self, state) -> int:
        """The mesh pjit program (multi-dataset path): a cold
        sharded_query compile mid-request is the same class of tail as
        a cold tier program."""
        eng = self.config.engine
        n = 0
        try:
            fault_point("device.bringup", "warmup_mesh")
            from .parallel.mesh import sharded_query

            mesh, stacked, arrays, _iof, _sof, _pof = state
            probe = QuerySpec("1", 1, 1, 1, 2)
            with device_warmup_phase():
                sharded_query(
                    arrays,
                    [probe],
                    mesh=mesh,
                    n_iters=stacked.n_iters,
                    window_cap=eng.window_cap,
                    record_cap=eng.record_cap,
                    aggregates_only=True,
                )
                n += 1
        except Exception:
            self._warm_failed("warmup_mesh", "mesh warmup failed")
        return n

    def close(self) -> None:
        """Release the scatter pool (same contract as
        DistributedEngine.close)."""
        self._scatter.shutdown(wait=False, cancel_futures=True)
        if self._batcher is not None:
            self._batcher.close()

    def datasets(self) -> list[str]:
        # the prebuilt serving list (base + delta tail) so a dataset
        # whose FIRST rows arrived as deltas is already routable
        return sorted({ds for ds, _vcf, _t in self._serve_list})

    def index_snapshot(
        self,
    ) -> list[tuple[tuple[str, str], object, object]]:
        """Sorted ``[((dataset_id, vcf_location), shard, plane_index),
        ...]`` under the publish lock: each shard with the exact planes
        of the same publish (never a concurrently re-ingested
        replacement)."""
        with self._mesh_lock:
            return [
                (k, v[0], v[2]) for k, v in sorted(self._indexes.items())
            ]

    def _plane_hbm_resident_locked(self, owner=None) -> int:
        """Plane bytes resident or reserved ON ONE CHIP, under the
        publish lock: ``owner``'s, or with None the fullest chip's
        (what a stack that lies on every chip has to fit beside) — THE
        one summation the budget gates share (the upload gate and
        ``_mesh_ready``'s stack gate), so the accounting can never
        disagree between them. The budget is a chip's: four planes of
        10 GB pass on four chips, two on one chip do not."""
        from .ops.plane_kernel import chip_of

        by_chip: dict[int, int] = {}
        for _s, _d, p in self._indexes.values():
            if p is not None:
                c = chip_of(p.device)
                by_chip[c] = by_chip.get(c, 0) + p.nbytes_hbm()
        for reserved_on, nbytes in self._plane_reserved.values():
            c = chip_of(reserved_on)
            by_chip[c] = by_chip.get(c, 0) + nbytes
        if owner is None:
            return max(by_chip.values(), default=0)
        return by_chip.get(chip_of(owner), 0)

    def plane_ledger(self) -> dict:
        """The HBM plane-budget ledger as a LOCK-FREE snapshot (the
        ``/device/status`` surface, ISSUE 14): resident per-dataset
        plane bytes, standing reservations (in-flight uploads) with
        their token count, and the
        budget headroom. The publish lock is only TRIED — when a stack
        rebuild holds it, the last computed snapshot serves with
        ``stale: true`` (the same answer-while-rebuilding discipline
        as ``/ops/digest``)."""
        budget = (
            getattr(self.config.engine, "plane_hbm_budget_gb", 11.0)
            * 1e9
        )
        got = self._mesh_lock.acquire(blocking=False)
        if got:
            try:
                self._plane_ledger_cache = {
                    "residentBytes": int(
                        sum(
                            p.nbytes_hbm()
                            for _s, _d, p in self._indexes.values()
                            if p is not None
                        )
                    ),
                    "reservedBytes": int(
                        sum(n for _o, n in self._plane_reserved.values())
                    ),
                    "reservedTokens": len(self._plane_reserved),
                    "fullestChipBytes": int(
                        self._plane_hbm_resident_locked()
                    ),
                }
            finally:
                self._mesh_lock.release()
        out = dict(self._plane_ledger_cache)
        out["budgetBytes"] = int(budget)
        # the budget is a chip's: the headroom is the fullest chip's
        out["headroomBytes"] = int(
            budget - out.get("fullestChipBytes", 0)
        )
        out["stale"] = not got
        return out

    def resident_bytes(self) -> dict:
        """{(chip, kind): bytes} of what this engine holds in HBM: the
        ``tiles`` and ``planes`` of every published key on the chip the
        arrays report, and per chip its slice of the mesh ``stack``.
        Lock-free over the copy-on-write serve list, like every
        diagnostic read."""
        from .ops.plane_kernel import chip_of

        out: dict[tuple, int] = {}

        def add(chip, kind, nbytes):
            out[(str(chip), kind)] = out.get((str(chip), kind), 0) + int(nbytes)

        for _ds, _vcf, (_s, dindex, planes) in self._serve_list:
            tiles = getattr(dindex, "tiles", None)
            if tiles is not None:
                add(chip_of(dindex.device), "tiles", tiles.nbytes)
            if planes is not None:
                add(chip_of(planes.device), "planes", planes.nbytes_hbm())
        state = self._mesh_state
        if state is not None:
            for arr in state[2].values():
                for piece in arr.addressable_shards:
                    add(chip_of(piece.device), "stack", piece.data.nbytes)
        return out

    def plane_fill(self) -> dict:
        """{chip: per cent} of the plane bytes resident on a chip that
        are the planes' own words (``n_rows x n_words x 4``); the rest
        is the zero padding of their lane rows: 100 at 1000 samples
        (four rows fill a lane row), 61.7 at 2504. Lock-free, as
        ``resident_bytes``."""
        from .ops.plane_kernel import chip_of

        own: dict[str, int] = {}
        held: dict[str, int] = {}
        for _ds, _vcf, (_s, _d, planes) in self._serve_list:
            if planes is not None:
                chip = str(chip_of(planes.device))
                own[chip] = own.get(chip, 0) + planes.logical_bytes()
                held[chip] = held.get(chip, 0) + planes.nbytes_hbm()
        return {c: 100.0 * own[c] / n for c, n in held.items() if n}

    def placement_table(self) -> list[dict]:
        """[{dataset, vcf, chip, bytes}] of every placed key, sorted:
        who owns what (``/debug/status`` serves it)."""
        from .ops.plane_kernel import chip_of

        with self._mesh_lock:
            placed = sorted(self._placement.items())
        return [
            {
                "dataset": ds,
                "vcf": vcf,
                "chip": chip_of(owner),
                "bytes": nbytes,
            }
            for (ds, vcf), (owner, nbytes) in placed
        ]

    def fused_stack_status(self) -> dict:
        """The fused cross-shard stack's state, lock-free (GIL-atomic
        reference reads — never the publish lock a rebuild may hold):
        built/dirty flags, fingerprint, age, and the stacked shape."""
        state = self._fused_state
        built_at = self._fused_built_at
        doc: dict = {
            "built": state is not None,
            "dirty": bool(self._fused_dirty),
            "fingerprint": self._base_fingerprint,
        }
        if state is not None:
            findex = state[0]
            doc["shards"] = findex.n_shards
            doc["rows"] = findex.n_rows
            doc["paddedRows"] = findex.n_padded
        if built_at is not None:
            doc["ageS"] = round(time.time() - built_at, 1)
        return doc

    def index_fingerprint(self) -> str:
        """FULL identity of the served data set — base shards AND the
        standing delta tail. Folds into async-query job keys and the
        worker ``/datasets`` identity, so any publish (base or delta)
        makes dependent caches re-execute. O(1): maintained under the
        publish lock, never recomputed on the query hot path."""
        return self._fingerprint

    def base_fingerprint(self) -> str:
        """Identity of the BASE shards only — stable across delta
        publishes, bumped by compaction/re-ingest. This is the
        staleness signal the warm dispatch stacks (the fused and the
        mesh state) key on: between
        compactions they keep serving base rows and only the delta
        tail pays per-shard dispatch."""
        return self._base_fingerprint

    def cache_fingerprint(self, dataset_ids) -> str:
        """The response-cache key's fingerprint component for a query
        over ``dataset_ids`` (empty = all loaded datasets): per-dataset
        BASE components only. Delta publishes deliberately leave it
        unchanged — their freshness is enforced by scoped invalidation
        — so a publish no longer rotates every key and resets the warm
        hit rate."""
        if not dataset_ids:
            return self._base_fingerprint
        ds_fp = self._ds_fingerprints
        return "&".join(
            f"{ds}={ds_fp.get(ds, '')}" for ds in sorted(set(dataset_ids))
        )

    def dataset_fingerprints(self) -> dict[str, str]:
        """Per-dataset identity — the same ``vcf|variant_count|
        call_count|n_rows`` components :meth:`index_fingerprint` folds,
        grouped by dataset, PLUS the delta-tail components. The worker
        ``/datasets`` endpoint serves this so a coordinator groups only
        IDENTICAL shard copies as replicas and routes around a worker
        serving a stale copy (dispatch._group_replicas) — a replica
        whose delta tail differs is not interchangeable. LOCK-FREE
        (copy-on-write snapshot): ``_mesh_ready`` holds the publish
        lock for the whole multi-second stack build, and a replica
        probe stalling behind it would read as a dead worker."""
        return dict(self._ds_full_fingerprints)

    def indexes_for(self, dataset_ids: list[str]):
        """Every serving (base + delta) triple for the datasets, in
        sorted key order. Delta entries carry a ``vcf#d<epoch>`` label
        so base and tail rows of one VCF stay distinct response keys
        (and never share a fused pre-match)."""
        for ds, vcf, triple in self._serve_list:
            if not dataset_ids or ds in dataset_ids:
                yield ds, vcf, triple

    @staticmethod
    def _delta_epoch_of(vcf_label: str) -> int:
        """-1 for a base serve-list label, else the ``#d<epoch>``."""
        _base, sep, epoch = vcf_label.rpartition("#d")
        if not sep:
            return -1
        try:
            return int(epoch)
        except ValueError:
            return -1

    def canary_brackets(self) -> dict[str, dict]:
        """Per-dataset known-answer probe source (canary.py): one
        representative row per dataset — canonical chromosome, exact
        start position and alt allele — whose presence the serving
        snapshot guarantees (the known-HIT bracket), plus the
        dataset's coordinate ceiling on that chromosome across every
        serving shard, so a bracket strictly beyond it is a known
        MISS. Rows come from the NEWEST serving shard that has a
        plain-allele row (delta tail first, base last): a probe
        derived from the freshest publish is exactly the staleness
        canary — a replica whose delta tail was lost or corrupted
        fails it. Lock-free over the copy-on-write serve list, like
        every diagnostic read."""
        serve = self._serve_list
        by_ds: dict[str, list[tuple[int, object, str]]] = {}
        ceilings: dict[tuple[str, str], int] = {}
        for ds, vcf, (shard, _di, _pl) in serve:
            by_ds.setdefault(ds, []).append(
                (self._delta_epoch_of(vcf), shard, vcf)
            )
            for chrom, _lo, hi in shard_regions(shard):
                key = (ds, chrom)
                ceilings[key] = max(ceilings.get(key, 0), hi)
        out: dict[str, dict] = {}
        for ds, shards in by_ds.items():
            # a PLAIN-allele row is REQUIRED for the hit probe: an
            # exact alternate_bases compare serves identically on every
            # dispatch path, while symbolic alts (<CN2>, <DEL>) only
            # match via variant_type queries — a symbolic hit probe
            # would be a permanent false canary.mismatch alarm. Walk
            # shards NEWEST first (deepest delta epoch down to base):
            # the freshest publish with a plain row anchors the probe,
            # so a symbolic-only delta does not silently drop the
            # coverage an older shard can still provide. A dataset
            # with no plain row in ANY shard gets the miss probe only.
            row = None
            chrom = None
            hit_shard = None
            source = None
            for _epoch, shard, vcf in sorted(
                shards, key=lambda t: t[0], reverse=True
            ):
                for rchrom, _lo, _hi in shard_regions(shard):
                    code = chromosome_code(rchrom)
                    lo = int(shard.chrom_offsets[code])
                    hi = int(shard.chrom_offsets[code + 1])
                    flags = np.asarray(shard.cols["flags"][lo:hi])
                    # ... and it has to be CALLED: a monomorphic row
                    # (AC 0) answers exists=False on every path, the
                    # same standing false alarm
                    plain = np.nonzero(
                        ((flags & FLAG.SYMBOLIC) == 0)
                        & (np.asarray(shard.cols["ac"][lo:hi]) > 0)
                    )[0]
                    if plain.size:
                        row = lo + int(plain[0])
                        chrom = rchrom
                        hit_shard = shard
                        source = vcf
                        break
                if row is not None:
                    break
            if chrom is None:
                # no plain row anywhere: anchor the miss bracket on
                # the newest shard's first populated region instead
                _e, shard, vcf = max(shards, key=lambda t: t[0])
                regions = shard_regions(shard)
                if not regions:
                    continue
                chrom = regions[0][0]
                source = vcf
            bracket = {
                "chrom": chrom,
                "maxEnd": ceilings[(ds, chrom)],
                "source": source,
            }
            if row is not None:
                alt = hit_shard.row_alt(row)
                bracket["pos"] = int(hit_shard.cols["pos"][row])
                bracket["alt"] = alt if alt else "N"
            out[ds] = bracket
        return out

    # -- query path ---------------------------------------------------------

    def search(self, payload: VariantQueryPayload) -> list[VariantSearchResponse]:
        """One response per (dataset, vcf) — the PerformQueryResponse set the
        reference's fan-in assembles (search_variants.py:130-155), computed
        without any fan-out machinery.

        Fronted by the fingerprint-keyed response cache: a repeated
        query (incl. a repeated MISS — negative entries) answers from
        host memory with zero device launches. Keys embed per-dataset
        BASE fingerprint components (``cache_fingerprint``) — a base
        publish rotates only the touched dataset's keys; a delta
        publish rotates none and instead scope-evicts the overlapping
        entries, so non-overlapping warm entries keep hitting across
        continuous ingest. The generation captured before dispatch
        stops a publish that lands mid-search from being outrun by a
        stale store."""
        # probe traffic may bypass the cache outright (payload flag):
        # a canary asserting freshness must read the live data plane,
        # not the answer the cache remembered
        cache = (
            None
            if getattr(payload, "no_response_cache", False)
            else self._response_cache
        )
        key = None
        scope = None
        gen = None
        if cache is not None:
            with stage("cache.lookup"):
                key = response_cache_key(
                    self.cache_fingerprint(payload.dataset_ids), payload
                )
                hit = cache.get(key)
                if hit is not None:
                    annotate(response_cache="hit")
                    plan_stage("cache", decision="hit")
                    return hit
                scope = response_cache_scope(payload)
                gen = cache.generation()
        outcome = "miss" if cache is not None else "off"
        annotate(response_cache=outcome)
        plan_stage("cache", decision=outcome)
        with span("engine.search") as sp:
            responses = self._search(payload, sp)
        if key is not None:
            cache.put(key, responses, scope=scope, gen=gen)
        return responses

    def cache_stats(self) -> dict | None:
        """Response-cache counters for /metrics; None when disabled."""
        return (
            None
            if self._response_cache is None
            else self._response_cache.stats()
        )

    def register_metrics(self, registry) -> None:
        """Register this engine's typed instruments — its own dispatch
        counters and stage quantiles, plus the batcher's and response
        cache's (the producers each own their registration; this only
        fans out to the components the engine wired)."""
        from .response_cache import register_cache_metrics

        registry.counter(
            "engine.fused_searches",
            "multi-dataset queries answered by one fused launch",
            fn=lambda: self.fused_searches,
        )
        registry.counter(
            "engine.mesh_searches",
            "queries answered by the one-pjit mesh path",
            fn=lambda: self.mesh_searches,
        )
        registry.counter(
            "engine.mesh_skips",
            "multi-dataset requests on a host with more than one device "
            "whose base targets did not all take the mesh stack, by "
            "reason (unbuilt / warming / failed / uncovered); thread "
            "scatter answered them",
            label="reason",
            fn=lambda: dict(self.mesh_skips),
        )
        registry.counter(
            "engine.selected_samples",
            "samples filtered requests selected, summed over their "
            "datasets: what the host work between the filter and the "
            "mask (engine.select) is proportional to",
            fn=lambda: self.selected_samples,
        )
        registry.counter(
            "engine.fanout_targets",
            "targets of multi-dataset requests served one pool task "
            "each (engine.fanout parks the request meanwhile)",
            fn=lambda: self.fanout_targets,
        )
        registry.counter(
            "engine.materialized",
            "targets by how their response came to be: skipped = "
            "answered from the launch's counts (it matched nothing "
            "there), inline = materialised on the request's thread, "
            "pooled = by a scatter-pool task; they add up to the "
            "targets asked",
            label="how",
            fn=lambda: dict(self.materialized),
        )
        registry.gauge(
            "device.plane_resident_bytes",
            "HBM bytes of the genotype planes resident per dataset, in "
            "whole 128-lane rows: n_rows x 512 B per 4096 samples above "
            "2048 samples, below that k = 128 // p rows to a lane row, "
            "p the words of a row rounded up to a power of two (what "
            "plane_hbm_budget_gb is spent on; hosts are sized by it)",
            fn=lambda: self.plane_ledger()["residentBytes"],
        )
        registry.gauge(
            "device.plane_fill",
            "per cent of a chip's resident plane bytes that are the "
            "planes' own words (n_rows x n_words x 4), the rest padding",
            label="chip",
            fn=self.plane_fill,
        )
        registry.gauge(
            "device.resident_bytes",
            "HBM bytes this engine holds resident by chip and kind: "
            "tiles and planes of the datasets the chip owns, stack = "
            "its slice of the mesh stack's columns",
            label=("chip", "kind"),
            fn=self.resident_bytes,
        )
        registry.counter(
            "placement.assignments",
            "keys given an owner chip (a republish keeps its owner and "
            "is not counted)",
            label="chip",
            fn=lambda: dict(self._assignments),
        )
        registry.gauge(
            "engine.materialize_ms",
            "host materialisation quantiles",
            label="quantile",
            fn=self._materialize_timing,
        )
        if self._batcher is not None:
            self._batcher.register_metrics(registry)
        register_cache_metrics(registry, lambda: self._response_cache)
        register_delta_metrics(registry, self.delta_metrics)

    @property
    def fanout_targets(self) -> int:
        """Targets requests had served from the scatter pool."""
        return self.materialized["pooled"]

    def _count_materialized(self, **by_how) -> None:
        """Once a request (and serving leg): its targets by how their
        responses came to be."""
        with self._mat_lock:  # unlocked += drops concurrent counts
            for how, n in by_how.items():
                self.materialized[how] += n

    def _materialize_timing(self) -> dict:
        """Host-materialisation quantiles alone (the
        ``engine.materialize`` stage's ring) — the gauge callback reads
        just this, so a /metrics render doesn't also pay the batcher's
        full per-stage summary."""
        return tracer.stage_quantiles("engine.materialize")

    def stage_timing(self) -> dict:
        """The full per-stage latency decomposition: the batcher's
        queue-wait/encode/launch/device/fetch quantiles (when a batcher
        serves) plus host materialisation — the stage after fetch —
        over the bounded windows. ``/debug/status`` reads this one dict
        to attribute a tail to a stage."""
        out: dict = {}
        if self._batcher is not None:
            out.update(self._batcher.timing_summary())
        out["materialize_ms"] = self._materialize_timing()
        return out

    def _fused_ready(self, wait: bool = False):
        """(FusedDeviceIndex, key->shard_id, key->shard-snapshot) over
        every warm device-served shard (XLA gather AND scatter-tile
        alike — the stack always dispatches through the XLA gather
        kernel, whose one launch beats k per-shard launches for a
        multi-dataset query on every backend), cached until the index
        set changes; None when fused dispatch is off, fewer than 2
        device shards are loaded, the stacked row count exceeds
        ``fused_max_rows`` (the stack duplicates ~48 B/row of device
        memory), a rebuild is still in flight (``wait=False``, the
        request path — the build runs on a background thread, never on
        a deadline-bounded request), or bring-up failed (per-shard
        dispatch then serves exactly as before). ``wait=True`` (warmup)
        builds inline and returns the fresh state."""
        eng = self.config.engine
        if not getattr(eng, "fused_dispatch", True):
            return None
        # LOCK-FREE fast path: when the state is clean, a device query
        # pays one bool + one reference read (GIL-atomic) — never the
        # shared _mesh_lock, which mesh/plane rebuilds can hold for
        # seconds. A reader racing a publish at worst sees the
        # pre-publish state, whose shard snapshot the route checks
        # (`shard_of[key] is shard`) make safe by construction.
        if not wait and not self._fused_dirty:
            return self._fused_state
        with self._mesh_lock:
            if not self._fused_dirty:
                state = self._fused_state
                if not wait or state is not None:
                    return state
                # wait=True with a build in flight (or a failed/skipped
                # one): rebuild inline anyway — warmup must come back
                # with the stack READY so the fused tier programs
                # compile now, not inside the first request. Duplicate
                # same-generation builds publish identical states.
            else:
                # claim the rebuild: snapshot inputs and mark clean
                # UNDER the lock, then build off-lock. While the build
                # runs, _fused_state is None and per-shard dispatch
                # serves; a concurrent caller sees dirty=False and
                # moves on instead of building a duplicate stack.
                self._fused_dirty = False
                self._fused_state = None
            gen = self._fused_gen
            keys = [
                k
                for k, (_s, d, _p) in sorted(self._indexes.items())
                if d is not None
            ]
            shards = [self._indexes[k][0] for k in keys]
        if len(keys) < 2:
            return None
        total = sum(s.n_rows for s in shards)
        max_rows = getattr(eng, "fused_max_rows", 64_000_000)
        if total > max_rows:
            logging.getLogger(__name__).info(
                "fused index skipped: %d stacked rows exceed "
                "fused_max_rows=%d; per-shard dispatch serves",
                total,
                max_rows,
            )
            return None
        if wait:
            # warmup/operator path: build on the caller's clock
            return self._build_fused(keys, shards, total, gen)
        # request path: a GB-scale stack takes seconds to build — never
        # on a deadline-bounded request thread. Per-shard dispatch
        # serves until the background build publishes.
        threading.Thread(
            target=self._build_fused,
            args=(keys, shards, total, gen),
            name="fused-build",
            daemon=True,
        ).start()
        return None

    def _build_fused(self, keys, shards, total, gen):
        """Build + publish the fused stack (request threads spawn this
        on a daemon thread; warmup runs it inline). ``gen`` is the
        publish generation the inputs were snapshotted at: publishing
        is refused if ANY _publish_index happened since — a slow build
        must never overwrite a newer stack (the dirty flag alone can't
        tell which claim a finished build belongs to)."""
        try:
            from .ops import FusedDeviceIndex

            findex = FusedDeviceIndex(shards)
        except Exception:
            _device_fallback(
                "fused_stack",
                "fused index unavailable; per-shard dispatch serves",
            )
            return None
        # the state carries its OWN shard snapshot (like the mesh
        # stack): stacked row ids are only valid against the exact
        # shard objects the stack was built from
        state = (
            findex,
            {k: i for i, k in enumerate(keys)},
            dict(zip(keys, shards)),
        )
        if self._keep_warm:
            # serving: the stack's tier programs compile before any
            # request can route to it (per-shard dispatch serves until
            # the publish below)
            self._warm_fused(findex)
        with self._mesh_lock:
            if self._fused_gen != gen:
                # a publish raced the build: this stack is already
                # stale — drop it; the next query rebuilds fresh
                return None
            self._fused_state = state
            self._fused_built_at = time.time()
        publish_event(
            "engine.fused_rebuild", shards=len(keys), rows=total
        )
        logging.getLogger(__name__).info(
            "fused index ready: %d shards, %d rows", len(keys), total
        )
        return state

    def _fused_route(self, key, shard):
        """(findex, shard_id) when the fused index covers this exact
        shard snapshot, else None."""
        if key is None:
            return None
        fst = self._fused_ready()
        if fst is None:
            return None
        findex, sid_of, shard_of = fst
        sid = sid_of.get(key)
        if sid is None or shard_of[key] is not shard:
            return None
        return findex, sid

    def _device_rows(
        self,
        shard: VariantIndexShard,
        dindex,
        spec: QuerySpec,
        *,
        ref_wildcard: bool = False,
        key: tuple | None = None,
    ) -> np.ndarray:
        """Matched row ids via the device kernel (micro-batched when
        enabled), host fallback on window/record overflow. When the
        fused stacked index covers this shard (``key``) and the shard
        is served by the XLA gather kernel, the query rides the fused
        index instead — concurrent queries against DIFFERENT datasets
        then coalesce into one accumulator and one launch. Scatter-tile
        shards keep their tuned per-shard kernel for single-target
        traffic (the fused stack still serves them for multi-dataset
        queries, where 1-launch-vs-k is structural — _fused_multi_rows).
        """
        from .ops import DeviceIndex

        eng = self.config.engine
        route = (
            self._fused_route(key, shard)
            if isinstance(dindex, DeviceIndex)
            else None
        )
        if self._batcher is not None:
            # concurrent searches coalesce into one kernel launch
            # (serving micro-batcher, SURVEY.md §7)
            if route is not None:
                findex, sid = route
                res = self._batcher.submit(
                    findex,
                    spec,
                    shard_id=sid,
                    window_cap=eng.window_cap,
                    record_cap=eng.record_cap,
                )
            else:
                res = self._batcher.submit(
                    dindex,
                    spec,
                    window_cap=eng.window_cap,
                    record_cap=eng.record_cap,
                )
        else:
            fault_point("kernel.launch")
            if route is not None:
                findex, sid = route
                res = run_queries_auto(
                    findex,
                    encode_queries([spec], shard_ids=[sid]),
                    window_cap=eng.window_cap,
                    record_cap=eng.record_cap,
                )
            else:
                res = run_queries_auto(
                    dindex,
                    [spec],
                    window_cap=eng.window_cap,
                    record_cap=eng.record_cap,
                )
        if res.overflow[0] or res.n_matched[0] > eng.record_cap:
            return host_match_rows(shard, spec, ref_wildcard=ref_wildcard)
        rows = res.rows[0][res.rows[0] >= 0]
        if route is not None:
            rows = route[0].to_local_rows(rows, route[1])
        return rows

    def _fused_multi_rows(self, targets, spec_base, payload):
        """{key: shard-local row ids | None} for every fused-covered
        target of a multi-dataset query, computed by ONE stacked-index
        launch (a None value marks window/record overflow — the caller
        host-matches that shard uncapped, the per-shard contract; an
        empty array a dataset the launch's counts say matched nothing:
        _launched_rows).

        Returns None (per-target dispatch serves) when the query needs
        host-only ref-wildcard semantics or fewer than 2 targets are
        covered by the fused index. Targets the one-dispatch fused
        match+planes kernel will serve (_fused_selected: scatter index
        + warm planes + device-exact ref) are excluded — their stacked
        pre-match would be computed and then thrown away. Dispatch
        errors (including injected ``kernel.launch`` faults and
        deadline expiry inside the batcher) propagate exactly as
        per-target dispatch errors would — the resilience envelope
        sees one identical failure surface.
        """
        if payload.selected_samples_only and not self._device_ref_ok(
            payload, spec_base
        ):
            return None
        # resolve the fused snapshot ONCE: resolving per target could
        # mix shard ids from two different stacks when a re-ingestion
        # rebuilds the state mid-loop, pairing rows with the wrong
        # shard_base (out-of-range local ids)
        fst = self._fused_ready()
        if fst is None:
            return None
        from .ops.scatter_kernel import ScatterDeviceIndex

        with stage("engine.plan"):
            wants_planes = self._wants_planes(payload)
            findex, sid_of, shard_of = fst
            routes = []
            for ds, vcf, shard, dindex, planes, _native in targets:
                if (
                    wants_planes
                    and planes is not None
                    and isinstance(dindex, ScatterDeviceIndex)
                ):
                    continue  # _fused_selected serves this target whole
                sid = sid_of.get((ds, vcf))
                if sid is not None and shard_of[(ds, vcf)] is shard:
                    routes.append(((ds, vcf), sid))
            if len(routes) < 2:
                return None
            eng = self.config.engine
            specs = [spec_base] * len(routes)
            sids = [sid for _k, sid in routes]
        if self._batcher is not None:
            res = self._batcher.submit_many(
                findex,
                specs,
                shard_ids=sids,
                window_cap=eng.window_cap,
                record_cap=eng.record_cap,
            )
        else:
            fault_point("kernel.launch")
            res = run_queries_auto(
                findex,
                encode_queries(specs, shard_ids=sids),
                window_cap=eng.window_cap,
                record_cap=eng.record_cap,
            )
        with stage("engine.plan"):
            out = _launched_rows(findex, routes, res, eng.record_cap)
        with self._mat_lock:  # unlocked += would drop concurrent counts
            self.fused_searches += 1
        annotate(dispatch="fused")
        return out

    def _search(self, payload: VariantQueryPayload, sp):
        with stage("engine.plan"):
            spec_base, targets = self._targets(payload)
        if not targets:
            return []
        return self._search_targets(payload, spec_base, targets, sp)

    def _targets(self, payload: VariantQueryPayload):
        """(spec, targets): the query as the kernels take it and the
        (dataset, vcf) shards it has to ask."""
        spec_base = QuerySpec(
            chrom=payload.reference_name,
            start_min=payload.start_min,
            start_max=payload.start_max,
            end_min=payload.end_min,
            end_max=payload.end_max,
            reference_bases=payload.reference_bases,
            alternate_bases=payload.alternate_bases,
            variant_type=payload.variant_type,
            variant_min_length=payload.variant_min_length,
            variant_max_length=payload.variant_max_length,
        )
        targets = []
        for ds, vcf, (shard, dindex, planes) in self.indexes_for(
            payload.dataset_ids
        ):
            native = shard.meta.get("chrom_native", {}).get(payload.reference_name)
            if native is None:
                # VCF has no matching chromosome: skipped, like the
                # get_matching_chromosome filter (search_variants.py:81-85)
                continue
            targets.append((ds, vcf, shard, dindex, planes, native))
        return spec_base, targets

    def _search_targets(self, payload, spec_base, targets, sp):
        # the submitting request's context: _one_target runs on the
        # scatter pool, whose threads do not inherit thread-locals —
        # re-installing it makes every charge (host rows, batcher
        # device share) and the batcher's lane note attribute to the
        # request instead of the unattributed residue
        req_ctx = current_context()

        # mesh serving covers the BASE shard snapshot it was built from;
        # the delta tail (and any racing republish) is excluded and
        # rides the per-shard scatter below — the base stack stays warm
        # across delta publishes instead of going cold per ingest
        mesh_responses: dict | None = None
        wants_planes = self._wants_planes(payload)
        on_owners = wants_planes and sum(
            1 for t in targets if t[4] is not None
        )
        if wants_planes and self._planes_declined:
            on_host = sum(
                1
                for t in targets
                if t[4] is None and (t[0], t[1]) in self._planes_declined
            )
            if on_host:
                # planes that should lie on their owner and do not: the
                # host's copy answers, and the request is COUNTED, so a
                # deployment whose planes fell off their chips cannot
                # look like one that holds them
                record_device_fallback("host_planes")
                plan_stage(
                    "fallback",
                    decision="host_planes",
                    reason="planes_budget",
                    datasets=on_host,
                )
        if len(targets) > 1 and on_owners:
            # the planes are resident ONCE, on their datasets' owner
            # chips: a request that reads them fans out below, one
            # match+planes launch per owner chip (its datasets a slot
            # each), the chips in parallel from the pool; the mesh
            # stack carries no second copy
            plan_stage(
                "mesh",
                decision="owner_fanout",
                reason="planes_on_owners",
                owners=on_owners,
            )
        elif len(targets) > 1:
            state = self._mesh_ready()
            shard_of = state[4] if state is not None else {}
            covered = [
                t for t in targets if shard_of.get((t[0], t[1])) is t[2]
            ]
            if len(covered) < len(targets):
                self._note_mesh_skip(state, targets, covered)
            if covered:
                try:
                    got = self._mesh_search(
                        state, covered, spec_base, payload, sp
                    )
                    mesh_responses = {
                        (t[0], t[1]): r for t, r in zip(covered, got)
                    }
                except Exception:
                    _device_fallback(
                        "mesh_search",
                        "mesh search failed; falling back to "
                        "thread scatter",
                    )
                    mesh_responses = None
        if mesh_responses is not None:
            targets = [
                t for t in targets if (t[0], t[1]) not in mesh_responses
            ]
            if not targets:
                plan_stage(
                    "split", decision="mesh_all", mesh=len(mesh_responses)
                )
                return list(mesh_responses.values())

        # the L0 leg of the three-way split: delta-tail targets the
        # mini-index covers ride ONE batched launch; everything it
        # does not cover (sub-threshold residue, racing republishes,
        # overflow marked None) is the host-scan residue. l0_pre_rows
        # owns the delta_shards charging rule: only host-walked tail
        # shards charge (L0-served targets pay device share through
        # the batcher's fetch-stage pro-rating instead)
        tail_targets = [
            ((t[0], t[1]), t[2]) for t in targets if "#d" in t[1]
        ]
        l0_rows = (
            self.l0_pre_rows(tail_targets, spec_base, payload)
            if tail_targets
            else {}
        )

        # cross-shard fused dispatch: ONE stacked-index launch answers
        # this query for every covered target (instead of one launch
        # per dataset); uncovered targets — including those the fused
        # match+planes kernel will serve whole (_fused_multi_rows
        # excludes them so their pre-match isn't computed and thrown
        # away) — fall through to their own path inside _one_target.
        pre_rows = (
            self._fused_multi_rows(targets, spec_base, payload)
            if len(targets) > 1
            else None
        )

        # the rows a launch already made left in hand, by target (the
        # L0 leg's before the fused stack's): None marks window/record
        # overflow -> the uncapped host matcher, the per-shard contract;
        # an empty array that the launch matched nothing (_launched_rows)
        rows_of = {**(pre_rows or {}), **l0_rows}

        # the fan-out's unit: the request's plane-reading targets that
        # share a launch group (one chip's plane datasets) are ONE unit,
        # one match+planes launch; every other target is a unit alone
        units = self._launch_units(payload, spec_base, targets)

        # the fan-in's ONE rule, read from what the launches returned
        # and what the payload asks. ``skipped``: the launch matched
        # nothing there, answered from that. ``ready``: rows in hand
        # and a response that reads no planes, microseconds of pure
        # host work on this thread. ``waiting``: a device round trip or
        # an uncapped host match still to pay (a plane group on its
        # owner chip, _device_rows, overflow), or planes to read in
        # numpy, which gives the interpreter lock up: for the pool to
        # overlap when there are two or more of them
        skipped, ready, waiting = [], [], []
        for unit in units:
            group, asked = unit
            r = (
                rows_of.get((asked[0][0], asked[0][1]))
                if group is None
                else None
            )
            if r is None:
                waiting.append(unit)
            elif not len(r):
                skipped.append(asked[0])
            elif wants_planes:
                waiting.append(unit)
            else:
                ready.append(unit)

        # the fan-out as decided on this thread: counts per serving
        # leg, and the targets whose launch overflowed (rows marked
        # None): they walk the host matcher instead
        n_l0 = sum(1 for r in l0_rows.values() if r is not None)
        n_in_hand = sum(1 for r in rows_of.values() if r is not None)
        plan_stage(
            "split",
            decision="fanout",
            mesh=len(mesh_responses) if mesh_responses else 0,
            l0=n_l0,
            fused=n_in_hand - n_l0,
            overflow_host=len(rows_of) - n_in_hand,
            scatter=len(targets),
            units=len(units),
            ready=len(ready),
            skipped=len(skipped),
        )

        def _one_unit(unit):
            """[(key, response)] of one unit, on the calling thread."""
            group, asked = unit
            with request_context(req_ctx):
                if group is None:
                    return [_one_target_inner(asked[0])]
                sels = {}
                for slot, (ds, _vcf, shard, *_rest) in asked.items():
                    sels[slot] = None
                    if payload.selected_samples_only:
                        with stage("engine.plan"):
                            sels[slot] = self._selection(shard, payload, ds)
                # fused match+planes program: the whole selected-samples
                # (or sample-extraction) leaf of every asked member in
                # ONE kernel dispatch (the reference worker's single
                # match+extract pass, search_variants.py:233-258). A
                # member it does not answer (overflow) falls through to
                # the split path, alone.
                got = self._fused_selected(
                    group, asked, spec_base, payload, sels
                )
                return [
                    _one_target_inner(t, (sels[slot], got.get(slot)))
                    for slot, t in asked.items()
                ]

        def _pooled_unit(unit):
            # the request's own thread is parked in ``engine.fanout``
            # for as long as the pool serves it: the pool's stages keep
            # their count and sum and add nothing to the chain's req_ms.
            # First the task's own wait for one of the pool's threads
            tracer.observe(
                "engine.pool_wait",
                (time.perf_counter() - t_pool) * 1e3,
                0,
                (req_ctx,),
            )
            with tracer.serving(0):
                return _one_unit(unit)

        def _one_target_inner(target, launched=None):
            """(key, response) of one target; ``launched`` is what its
            group's launch left it: (its selection, its rows and plane
            reductions or None for the split path)."""
            ds, vcf, shard, dindex, planes, native = target
            selected_idx = None
            fused = None
            rows = None
            if launched is not None:
                selected_idx, got = launched
                if got is not None:
                    rows, fused = got
            elif payload.selected_samples_only:
                with stage("engine.plan"):
                    selected_idx = self._selection(shard, payload, ds)
            if rows is None and (ds, vcf) in rows_of:
                # the L0 mini-index's or the fused stack's launch
                # already matched this target (an L0 overflow's host
                # walk is already charged above)
                r = rows_of[(ds, vcf)]
                rows = (
                    r
                    if r is not None
                    else host_match_rows(
                        shard,
                        spec_base,
                        ref_wildcard=payload.selected_samples_only,
                    )
                )
            if rows is None and payload.selected_samples_only:
                # selected-samples leaf (reference performQuery/
                # lambda_function.py:43-46 switches to
                # search_variants_in_samples): row matching runs on device
                # unless the ref carries an N wildcard (the one field where
                # the in-samples regex semantics diverge from the exact
                # kernel compare); counting is then sample-restricted in
                # materialize_response via the genotype bit planes
                if dindex is not None and self._device_ref_ok(
                    payload, spec_base
                ):
                    rows = self._device_rows(
                        shard,
                        dindex,
                        spec_base,
                        ref_wildcard=True,
                        key=(ds, vcf),
                    )
                else:
                    rows = host_match_rows(
                        shard, spec_base, ref_wildcard=True
                    )
            elif rows is None and dindex is None:
                rows = host_match_rows(shard, spec_base)
            elif rows is None:
                rows = self._device_rows(
                    shard, dindex, spec_base, key=(ds, vcf)
                )
            with stage("engine.materialize"):
                return (ds, vcf), materialize_response(
                    shard,
                    rows,
                    payload,
                    chrom_label=native,
                    dataset_id=ds,
                    vcf_location=vcf,
                    selected_idx=selected_idx,
                    plane_index=planes,
                    fused=fused,
                )

        # two or more waiting units: a pool task each (the reference's
        # ThreadPoolExecutor(500) per-dataset dispatch,
        # search_variants.py:77-118), submitted first: their device
        # round trips (one a chip) overlap each other and this thread's
        # work. One runs here, its kernel.* stages in the request's chain
        t_pool = time.perf_counter()
        pooled = (
            self._scatter.map(_pooled_unit, waiting)
            if len(waiting) > 1
            else None
        )
        got = []
        if skipped:
            with stage("engine.materialize"):
                got += [((t[0], t[1]), _no_match(t)) for t in skipped]
        for unit in (ready + waiting if pooled is None else ready):
            got += _one_unit(unit)
        n_pooled = 0
        if pooled is not None:
            with stage("engine.fanout"):
                for krs in pooled:
                    got += krs
            n_pooled = sum(len(asked) for _group, asked in waiting)
        self._count_materialized(
            skipped=len(skipped),
            inline=len(targets) - len(skipped) - n_pooled,
            pooled=n_pooled,
        )
        by_target = dict(got)
        responses = [by_target[(t[0], t[1])] for t in targets]
        if mesh_responses is not None:
            # reassemble mesh-served base responses + scatter-served
            # tail in the original sorted target order
            by_key = dict(mesh_responses)
            by_key.update(
                {(t[0], t[1]): r for t, r in zip(targets, responses)}
            )
            responses = [by_key[k] for k in sorted(by_key)]
        sp.note(targets=len(targets), responses=len(responses))
        return responses

    @staticmethod
    def _wants_planes(payload) -> bool:
        """Queries whose response READS genotype planes: the selected-
        samples leaf, or sample-hit extraction on record/aggregated
        shapes WITH details (materialize's extraction block requires
        include_details). Everything else never touches the planes and
        takes the (micro-batched) match-only path."""
        return payload.selected_samples_only or (
            payload.include_samples
            and payload.include_details
            and payload.requested_granularity in ("record", "aggregated")
        )

    def _launch_units(self, payload, spec_base, targets) -> list:
        """The units a request's targets are served in: ``[(group,
        {slot: target})]``. The targets whose response reads planes the
        fused match+planes program can reach (planes resident on their
        owner chip, a scatter index, a device-exact ref) are gathered
        by launch group (``_plane_groups``: the plane datasets of one
        chip), a unit a group; a group only one of whose members is
        asked is launched as that member alone, and so is a target
        whose published buffers the map does not hold yet (a publish
        the regrouping has not caught up with). Every other target is
        a unit alone, ``group`` None."""
        from .ops.scatter_kernel import ScatterDeviceIndex

        reads_planes = self._wants_planes(payload) and self._device_ref_ok(
            payload, spec_base
        )
        groups = self._plane_groups
        units: list = []
        of_group: dict = {}
        for t in targets:
            ds, vcf, _shard, dindex, planes, _native = t
            if not (
                reads_planes
                and planes is not None
                and isinstance(dindex, ScatterDeviceIndex)
            ):
                units.append((None, {0: t}))
                continue
            group, slot = groups.get((ds, vcf), (None, 0))
            if (
                group is None
                or group[slot][1] is not dindex
                or group[slot][2] is not planes
            ):
                units.append(((((ds, vcf), dindex, planes),), {0: t}))
                continue
            asked = of_group.get(id(group))
            if asked is None:
                asked = of_group[id(group)] = {}
                units.append((group, asked))
            asked[slot] = t
        return [
            ((group[next(iter(asked))],), {0: next(iter(asked.values()))})
            if group is not None and len(asked) == 1 and len(group) > 1
            else (group, asked)
            for group, asked in units
        ]

    def _fused_selected(self, group, asked, spec_base, payload, sels):
        """ONE-dispatch match + plane reduction for the request's
        targets in one launch group via the fused kernel.

        ``asked``: {slot: target} of the group's members the request
        asks; ``sels``: {slot: its resolved selection or None}. Returns
        {slot: (rows, (pc_call, pc_tok, or_words))} for
        materialize_response. A slot left out must take the split path:
        window/record overflow (the uncapped host matcher then answers,
        exactly like the match kernel's overflow contract), or every
        slot when the launch failed (counted).
        """
        from .ops.scatter_kernel import run_selected_group

        eng = self.config.engine
        first = group[0][2]
        # the selection's own mask words (resolved once a request and
        # dataset), or every sample; a member nobody asks is a padding
        # slot
        masks = [None] * len(group)
        for slot in asked:
            masks[slot] = (
                sels[slot].mask
                if sels[slot] is not None
                else np.full(first.n_words, 0xFFFFFFFF, np.uint32)
            )
        try:
            fault_point("device.bringup", "fused_selected")
            res = run_selected_group(
                [(d, p) for _k, d, p in group],
                spec_base,
                masks,
                window_cap=eng.window_cap,
                record_cap=eng.record_cap,
                with_counts=(
                    payload.selected_samples_only and first.has_counts
                ),
            )
        except Exception:
            _device_fallback(
                "fused_selected",
                "fused selected kernel failed; split path serves",
            )
            return {}
        out = {}
        for slot in asked:
            if res.overflow[slot]:
                continue
            keep = res.rows[slot] >= 0
            out[slot] = (
                res.rows[slot][keep].astype(np.int64),
                (
                    res.pc_call[slot][keep],
                    res.pc_tok[slot][keep],
                    res.or_words[slot],
                ),
            )
        return out

    # -- mesh serving path --------------------------------------------------

    @staticmethod
    def _selected_idx(shard, payload, ds: str) -> list[int]:
        """Positions of the request's samples in the shard's planes, in
        the request's order, through the map the shard keeps (a name it
        does not know is dropped). One C-level pass of dictionary reads:
        18,191 of them at biobank width, and none over the cohort."""
        found = list(
            map(shard.sample_positions().get, payload.sample_names.get(ds, ()))
        )
        if None in found:
            found = [k for k in found if k is not None]
        return found

    def _selection(self, shard, payload, ds: str) -> SampleSelection:
        """The request's selection on ``shard`` (stage ``engine.select``,
        counter ``engine.selected_samples``), resolved once a request
        and dataset: the launch's mask is the mask the response is
        materialised under. Names the metadata memo handed out
        (``metadata.memo.KeptSamples``) carry what was resolved from
        them, by shard: every request of one filter list and metadata
        generation reads it there (resolved a request, 18,191 reads of a
        454,787-entry dictionary under eight threads halve the rate of
        ``ukb1.samples``), and it goes with the memo's entry. A
        dataset published again is another shard; names from anywhere
        else (a worker's decoded payload, a test's list) are resolved a
        request."""
        with stage("engine.select"):
            wanted = payload.sample_names.get(ds, ())
            kept = getattr(wanted, "resolved", None)
            ref, sel = (kept or {}).get(id(shard), (None, None))
            if ref is None or ref() is not shard:
                sel = SampleSelection(
                    shard, self._selected_idx(shard, payload, ds)
                )
                if kept is not None:
                    for key, (gone, _sel) in list(kept.items()):
                        if gone() is None:  # a shard since retired
                            kept.pop(key, None)
                    kept[id(shard)] = (weakref.ref(shard), sel)
        with self._mat_lock:  # unlocked += drops concurrent counts
            self.selected_samples += len(sel)
        return sel

    @staticmethod
    def _device_ref_ok(payload, spec_base) -> bool:
        """Device row-matching is exact for selected-samples queries unless
        the query ref carries an N wildcard (regex semantics, host only)."""
        if not payload.selected_samples_only:
            return True
        ref = spec_base.reference_bases
        return ref is None or "N" not in ref.upper()

    def _note_mesh_skip(self, state, targets, covered) -> None:
        """Count a multi-dataset request whose BASE targets did not all
        take the mesh stack, on a host where they should have (mesh
        serving on, more than one device): ``engine.mesh_skips{reason}``
        beside a ``plan_stage``, so a deployment whose stack never came
        up cannot look like one that has it. Reasons: the state reads
        None (``unbuilt``: no warm-up has built it yet; ``warming``: its
        programs are compiling and thread scatter serves meanwhile;
        ``failed``: bring-up raised, which also ticked
        ``device.fallbacks{mesh_stack}``), or the stack stands and does
        not hold a base shard the request targets (``uncovered``: a
        publish the rebuild has not caught up with). The delta tail
        rides the L0 index by design and is no skip. A skip is NOT a
        counted fall-back: the stack is rebuilt and warmed OFF the
        request path after every publish (a /submit, a fold), thread
        scatter answers right meanwhile, and the smoke and the soak
        tests hold ``device.fallbacks`` at zero across exactly those
        windows; a cell that should never see one reads
        ``mesh_launches_per_query``."""
        covered_keys = {(t[0], t[1]) for t in covered}
        missed = sum(
            1
            for t in targets
            if "#d" not in t[1] and (t[0], t[1]) not in covered_keys
        )
        eng = self.config.engine
        if not missed or not eng.use_mesh or not eng.use_tpu:
            return
        import jax

        if len(jax.devices()) < 2:
            return
        reason = self._mesh_why if state is None else "uncovered"
        with self._mat_lock:
            self.mesh_skips[reason] = self.mesh_skips.get(reason, 0) + 1
        # the plan's reasons are a registry of literals (plan.PLAN_REASONS)
        if state is None:
            plan_stage(
                "mesh", decision="skipped", reason="unbuilt",
                why=reason, targets=missed,
            )
        else:
            plan_stage(
                "mesh", decision="skipped", reason="stale", targets=missed
            )

    def _mesh_ready(self):
        """(mesh, stacked, device_arrays, key->stack-position), built over
        ALL loaded shards and cached until the index set changes; None when
        mesh serving is off, <2 devices are visible, or bring-up failed
        (thread-scatter then serves)."""
        eng = self.config.engine
        if not eng.use_mesh or not eng.use_tpu:
            return None
        with self._mesh_lock:
            if not self._mesh_dirty:
                return self._mesh_state
            self._mesh_state = None
            self._mesh_dirty = False
            self._mesh_why = "unbuilt"
            gen = self._fused_gen  # bumped by every base publish
            state = None
            try:
                import jax

                from .parallel.mesh import StackedIndex, make_mesh

                if len(jax.devices()) < 2 or len(self._indexes) < 2:
                    return None
                mesh = make_mesh()
                keys = sorted(self._indexes)
                shards = [self._indexes[k][0] for k in keys]
                n_mesh = int(mesh.devices.size)
                d_pad = -(-len(shards) // n_mesh) * n_mesh
                # columns only: a dataset's planes are resident ONCE,
                # on its owner chip (PlaneDeviceIndex), and
                # _search_targets fans plane-reading requests out to
                # the owners; the stack serves booleans and counts
                stacked = StackedIndex(shards, n_datasets_padded=d_pad)
                arrays = stacked.shard_to_mesh(mesh)
                # the state carries its OWN shard snapshot: row ids from
                # the stacked arrays are only valid against the exact
                # shard objects the stack was built from, never against
                # a concurrently re-ingested replacement
                shard_of = dict(zip(keys, shards))
                planes_of = {k: self._indexes[k][2] for k in keys}
                index_of = {k: i for i, k in enumerate(keys)}
                state = (
                    mesh, stacked, arrays, index_of, shard_of, planes_of
                )
            except Exception:
                self._mesh_why = "failed"
                _device_fallback(
                    "mesh_stack",
                    "mesh serving unavailable; using thread scatter",
                )
            if state is None or not self._keep_warm:
                self._mesh_state = state
                return state
            self._mesh_why = "warming"
        # serving: compile the stack's programs OFF the lock before any
        # request can route to it. Meanwhile the state reads clean and
        # empty, so thread scatter serves; a base publish that raced
        # the compile wins (the stack is stale, the next call rebuilds)
        self._warm_mesh(state)
        with self._mesh_lock:
            if self._fused_gen == gen:
                self._mesh_state = state
            return self._mesh_state

    def _mesh_search(self, state, targets, spec_base, payload, sp):
        """Multi-dataset query as ONE compiled program over the dataset-
        sharded stack: every device answers the query against its local
        shards and the cross-dataset aggregates fan in with psum — the
        reference's 500-thread scatter + DynamoDB counter barrier
        (search_variants.py:77-118, variant_queries.py:45-59) as a single
        pjit dispatch. Per-dataset row ids come back device-sharded and
        materialise host-side with the same cumulative semantics as the
        scatter path.

        The launch runs on the REQUEST's thread, beside the
        micro-batcher, under the kernel stages every family passes
        (``sharded_query``); concurrent requests launch the collective
        program concurrently, which four v5e chips serve (PR 34: 800
        launches a window from four clients, every answer exact; only
        XLA:CPU needs ``mesh._collective_guard``). The launch's own
        counts then decide what is materialised: a target it matched
        nothing in is answered from the count (one ``engine.materialize``
        scope around all of them, ``engine.materialized{skipped}``);
        the others, one ``engine.materialize`` each, run on this thread
        where the request reads no planes (booleans and counts: the
        stack's traffic), and otherwise on the scatter pool exactly as
        the per-target fan-out's do (this thread parked in
        ``engine.fanout``, each task's wait in ``engine.pool_wait``,
        ``engine.fanout_targets`` ticked). The device's own ``agg`` is
        computed and only noted: answering booleans and counts from it
        WITHOUT the per-dataset responses would take away what
        ``search`` returns a dataset (PERF.md 7) and is not done."""
        from .parallel.mesh import sharded_query

        mesh, stacked, arrays, index_of, shard_of, planes_of = state
        eng = self.config.engine
        device_ref_ok = self._device_ref_ok(payload, spec_base)
        ref_wild = payload.selected_samples_only
        # the stack carries columns only: it matches rows, and a
        # request that reads planes arrives here only where no target
        # has them on its owner (device_planes off, or declined and
        # counted in _search_targets): materialisation then reads the
        # host's planes
        per_ds, agg = sharded_query(
            arrays,
            [spec_base],
            mesh=mesh,
            n_iters=stacked.n_iters,
            window_cap=eng.window_cap,
            record_cap=eng.record_cap,
            n_datasets=stacked.n_datasets,
        )
        req_ctx = current_context()
        # ONE vector test over the launch's own counts, as the fused
        # leg's (_launched_rows): ``host`` where the uncapped host
        # matcher has to answer (window overflow, more matches than
        # record_cap, a ref the device cannot compare exactly), ``miss``
        # where the launch matched nothing: answered from the count, no
        # pick of its rows, no call (a point query over 128 datasets
        # hits one of them or none). A key the stack does not hold
        # raises here: thread scatter then serves, counted by the caller
        at = [index_of[(t[0], t[1])] for t in targets]
        n_matched = per_ds["n_matched"][at, 0]
        host = per_ds["overflow"][at, 0] | (n_matched > eng.record_cap)
        if not device_ref_ok:
            host[:] = True
        miss = (~host & (n_matched == 0)).tolist()
        host, n_matched = host.tolist(), n_matched.tolist()

        def _one(i):
            ds, vcf, _shard, _dindex, _planes, native = targets[i]
            # the stage covers the whole of a target's response, the
            # pick of its rows from the launch's leaves included: built
            # on the request's thread, 128 such picks between stages
            # were a fifth of a request that no stage named
            # (span_coverage 76.9 %, PERF.md 6, PR 34)
            with stage("engine.materialize"):
                # state-consistent shard: the stack's rows materialise
                # against the shard the stack was built from
                shard = shard_of[(ds, vcf)]
                selected_idx = (
                    self._selection(shard, payload, ds)
                    if payload.selected_samples_only
                    else None
                )
                if host[i]:
                    rows = host_match_rows(
                        shard, spec_base, ref_wildcard=ref_wild
                    )
                else:
                    # the matched row ids come first (_query_one): the
                    # pick costs what matched, not record_cap words
                    rows = per_ds["rows"][at[i], 0, : n_matched[i]].astype(
                        np.int64
                    )
                return materialize_response(
                    shard,
                    rows,
                    payload,
                    chrom_label=native,
                    dataset_id=ds,
                    vcf_location=vcf,
                    selected_idx=selected_idx,
                    plane_index=planes_of.get((ds, vcf)),
                )

        def _pooled(i):
            # as the per-target fan-out of _search_targets: the
            # request's thread is parked in ``engine.fanout`` while the
            # pool serves it, so the pool's stages add nothing to the
            # chain's req_ms; first the task's wait for a pool thread
            tracer.observe(
                "engine.pool_wait",
                (time.perf_counter() - t_pool) * 1e3,
                0,
                (req_ctx,),
            )
            with tracer.serving(0), request_context(req_ctx):
                return _one(i)

        responses: list = [None] * len(targets)
        if any(miss):
            with stage("engine.materialize"):  # ONE scope, all the misses
                responses = [
                    _no_match(t) if m else None
                    for t, m in zip(targets, miss)
                ]
        todo = [i for i, m in enumerate(miss) if not m]
        how = "pooled"
        if len(todo) < 2 or not self._wants_planes(payload):
            # the rows are in hand and a boolean's or a count's
            # response is microseconds of pure host work: on this
            # thread, as _search_targets' ready units. A pool task a
            # target was 128 thread hand-overs a request in
            # ``mds4.fanout`` (39 ms parked in engine.fanout for 1.3 ms
            # of materialising) and shed the cell (PERF.md 6, PR 34)
            how = "inline"
            for i in todo:
                responses[i] = _one(i)
        else:
            # plane-reading materialisations read the host's planes in
            # numpy, which gives the interpreter lock up: the pool
            # overlaps them, as the per-target fan-out's
            with stage("engine.fanout"):
                t_pool = time.perf_counter()
                for i, r in zip(todo, self._scatter.map(_pooled, todo)):
                    responses[i] = r
        self._count_materialized(
            skipped=len(targets) - len(todo), **{how: len(todo)}
        )
        with self._mat_lock:
            self.mesh_searches += 1
        annotate(dispatch="mesh")
        sp.note(
            targets=len(targets),
            responses=len(responses),
            mesh=int(mesh.devices.size),
            psum_exists=bool(agg["exists"][0]),
        )
        return responses
