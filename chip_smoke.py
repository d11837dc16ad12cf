#!/usr/bin/env python3
"""Serve a 1000-Genomes-shaped beacon end to end on the chip, once.

``python3 chip_smoke.py`` is the quickest proof that the system still
starts on the TPU. ONE process: it builds the native library from
source, generates its data from ``--seed`` under a scratch directory,
starts the server through the functions ``python -m
sbeacon_tpu.api.server`` calls (``build_app`` / ``warm_app``), and then
acts as its HTTP client: ``POST /submit`` of a bgzipped cohort VCF, a
few dozen ``/g_variants`` requests over every query shape, the entity
and probe routes, and a delta tail queried while it stands (the L0
index) and again once the compactor has folded it. The server runs on
its defaults; the one thing set is the submit token. Every variant
answer is compared with the plain reference (``host_match_rows`` + the
per-record loop spec on the synthetic shards, ``oracle/cpu_oracle.py``
over the VCF's own records for the submitted dataset), and the
server's own surfaces must show that the device did the work: index
classes, launches by family, zero compiles inside a request once
``warm_app`` has returned, zero counted fallbacks, HBM in use.

It exits non-zero when no TPU is visible, when any stage raises, when
an answer differs from the reference, or when a fallback counter is
non-zero. No stage is wrapped in a handler that lets the run go on.

It prints two lines on stdout: a summary of what the run saw (set-up
times and counts, NOT benchmark numbers; also written under ``--out``),
then, last, the verdict alone:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.

The deployment (sizes cut only by scale, never by width):
  (a) an index corpus of 2e7 rows over
      chr1-22 at 2504 samples, one dataset on one chip, ``2n-2``
      datasets on ``n`` chips (same total rows);
  (b) a genotype-plane dataset at the full 2504-sample width, 2e6 rows
      (a tenth of (a): rows are cut, widths are not);
  (c) a 25,000-record, 2504-sample cohort VCF, submitted over HTTP;
  (d) a delta tail of four 2,000-row shards at the same width, the
      size of the slices a streaming ingest publishes.

``--rehearsal`` runs the same script at toy sizes on whatever platform
JAX finds, names that platform, and can never print the success line
of a chip run (``"ok"`` stays false).
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import dataclasses
import http.client
import json
import os
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

FULL = {"index_rows": 20_000_000, "plane_rows": 2_000_000, "vcf_records": 25_000}
REHEARSAL = {"index_rows": 60_000, "plane_rows": 6_000, "vcf_records": 4_200}
#: the delta tail of (d), at every size: a tail is small by nature. Four
#: shards reach l0_min_shards and stay under delta_max_shards, and 8,000
#: rows keep every L0 build on one padded shape (one set of programs)
DELTA_DS = "kgd"
DELTA_SHARDS = 4
DELTA_ROWS = 2_000
N_SAMPLES = 2504
ASSEMBLY = "GRCh38"
VCF_CHROM = "20"
#: samples of the submitted cohort that get metadata (and so can be
#: selected by a filter): enough to exercise the leaf, cheap to submit
VCF_ENTITIES = 32
SEX_TERMS = ("NCIT:C16576", "NCIT:C20197")  # female, male
VCF_TERM = "HP:0001626"

DEFAULT_OUT = Path(__file__).resolve().parent / "chiprun_out"


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Stages:
    """Wall seconds per named stage (set-up times, not a benchmark)."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        print(f"[chip_smoke] {name} ...", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.seconds[name] = round(self.seconds.get(name, 0.0) + dt, 2)
            print(f"[chip_smoke] {name} {dt:.1f}s", file=sys.stderr, flush=True)


def cache_entries(cache_dir: Path) -> int:
    return sum(1 for p in cache_dir.rglob("*") if p.is_file())


# -- data ---------------------------------------------------------------------


def make_datasets(sizes: dict, seed: int, n_dev: int, scratch: Path, stage):
    """{dataset_id: shard} for (a) and (b), the VCF path of (c), the
    delta shards of (d)."""
    from sbeacon_tpu.genomics.tabix import ensure_index
    from sbeacon_tpu.harness.genome1k import write_cohort_vcf
    from sbeacon_tpu.testing import synthetic_shard

    n_index = 1 if n_dev == 1 else 2 * n_dev - 2
    per = -(-sizes["index_rows"] // n_index)
    shards = {}
    with stage("gen_index"):
        for i in range(n_index):
            ds = f"kg-{i}"
            shards[ds] = synthetic_shard(
                per, n_samples=N_SAMPLES, seed=seed + i, dataset_id=ds
            )
    with stage("gen_planes"):
        # (b), and on several chips one more like it per further chip
        # (no metadata, no checks of their own): planes are most of
        # what a chip holds, so a plane dataset a chip lets the
        # placement come out even
        for i in range(n_dev):
            ds = "kgp" if i == 0 else f"kgq-{i}"
            shards[ds] = synthetic_shard(
                sizes["plane_rows"],
                n_samples=N_SAMPLES,
                seed=seed + 100 + i,
                dataset_id=ds,
                with_gt_planes=True,
                plane_density=0.25,
            )
    with stage("gen_vcf"):
        vcf = scratch / "cohort_chr20.vcf.gz"
        stats = write_cohort_vcf(
            vcf,
            chrom=VCF_CHROM,
            n_records=sizes["vcf_records"],
            n_samples=N_SAMPLES,
            seed=seed + 200,
        )
        ensure_index(vcf)
    with stage("gen_deltas"):
        deltas = [
            synthetic_shard(
                DELTA_ROWS,
                n_samples=N_SAMPLES,
                seed=seed + 300 + i,
                dataset_id=DELTA_DS,
                with_gt_planes=True,
                plane_density=0.25,
            )
            for i in range(DELTA_SHARDS)
        ]
    return shards, vcf, stats, deltas


def metadata_submission(ds: str, samples: list[str], term_of, vcfs=()):
    """One /submit body: the dataset doc plus one individual ->
    biosample -> run -> analysis chain per sample."""
    body = {
        "datasetId": ds,
        "assemblyId": ASSEMBLY,
        "vcfLocations": [str(v) for v in vcfs],
        "dataset": {"name": ds, "description": "chip_smoke"},
        "index": True,
    }
    if samples:
        idx = range(len(samples))
        body["individuals"] = [
            {
                "id": f"{ds}-I{i}",
                "sex": {"id": SEX_TERMS[i % 2], "label": "-"},
                "diseases": [{"diseaseCode": {"id": term_of(i)}}],
            }
            for i in idx
        ]
        body["biosamples"] = [
            {"id": f"{ds}-B{i}", "individualId": f"{ds}-I{i}"} for i in idx
        ]
        body["runs"] = [
            {
                "id": f"{ds}-R{i}",
                "biosampleId": f"{ds}-B{i}",
                "individualId": f"{ds}-I{i}",
            }
            for i in idx
        ]
        body["analyses"] = [
            {
                "id": f"{ds}-A{i}",
                "runId": f"{ds}-R{i}",
                "biosampleId": f"{ds}-B{i}",
                "individualId": f"{ds}-I{i}",
                "vcfSampleId": samples[i],
            }
            for i in idx
        ]
    return body


# -- the plain reference ------------------------------------------------------


def query_spec(p):
    """The kernel-level query of a payload."""
    from sbeacon_tpu.ops.kernel import QuerySpec

    return QuerySpec(
        p.reference_name, p.start_min, p.start_max, p.end_min, p.end_max,
        p.reference_bases, p.alternate_bases, p.variant_type,
        p.variant_min_length, p.variant_max_length,
    )


def selected_positions(names: list[str], payload, ds: str):
    """Column positions of the payload's selected samples, in the
    payload's order (None when the query selects nobody)."""
    if not payload.selected_samples_only:
        return None
    pos_of = {s: k for k, s in enumerate(names)}
    return [pos_of[s] for s in payload.sample_names.get(ds, []) if s in pos_of]


class Reference:
    """Expected per-dataset answers, independent of the device path:
    the uncapped numpy matcher + the per-record loop spec on the
    synthetic shards, the CPU oracle over the VCF's own records (read
    by the pure-Python BGZF/tabix code) for the submitted dataset."""

    def __init__(self, shards: dict, vcf_ds: str, vcf: Path, vcf_samples):
        self.shards = shards
        self.vcf_ds = vcf_ds
        self.vcf = vcf
        self.vcf_samples = vcf_samples
        #: dataset -> [(serving label, delta shard)] while a tail stands:
        #: every delta shard answers for itself, under its own label
        self.tails: dict[str, list] = {}

    def responses(self, payload) -> list:
        out = []
        for ds in sorted(payload.dataset_ids):
            if ds == self.vcf_ds:
                got = [self._vcf(ds, payload)]
            elif ds in self.tails:
                got = [
                    self._shard(shard, ds, payload, label)
                    for label, shard in self.tails[ds]
                ]
            else:
                shard = self.shards[ds]
                got = [
                    self._shard(
                        shard, ds, payload, shard.meta["vcf_location"]
                    )
                ]
            # None: no such chromosome there
            out.extend(r for r in got if r is not None)
        return out

    def _vcf(self, ds: str, p):
        from sbeacon_tpu.genomics.vcf import iter_vcf_records
        from sbeacon_tpu.oracle.cpu_oracle import oracle_search

        if p.reference_name != VCF_CHROM:
            return None
        selected = selected_positions(self.vcf_samples, p, ds)
        names = self.vcf_samples
        if selected is not None:
            names = [names[k] for k in selected]
        return oracle_search(
            iter_vcf_records(
                self.vcf, region=(VCF_CHROM, p.start_min, p.start_max)
            ),
            first_bp=p.start_min,
            last_bp=p.start_max,
            end_min=p.end_min,
            end_max=p.end_max,
            reference_bases=p.reference_bases,
            alternate_bases=p.alternate_bases,
            variant_type=p.variant_type,
            variant_min_length=p.variant_min_length,
            variant_max_length=p.variant_max_length,
            requested_granularity=p.requested_granularity,
            include_details=p.include_details,
            include_samples=p.include_samples,
            sample_names=names,
            dataset_id=ds,
            vcf_location=str(self.vcf),
            chrom_label=VCF_CHROM,
            selected_sample_idx=selected,
        )

    @staticmethod
    def _shard(shard, ds: str, p, vcf_location: str):
        from sbeacon_tpu.engine import (
            host_match_rows,
            materialize_response_loop,
        )

        native = shard.meta["chrom_native"].get(p.reference_name)
        if native is None:
            return None
        rows = host_match_rows(
            shard, query_spec(p), ref_wildcard=p.selected_samples_only
        )
        return materialize_response_loop(
            shard,
            rows,
            p,
            chrom_label=native,
            dataset_id=ds,
            vcf_location=vcf_location,
            selected_idx=selected_positions(
                shard.meta["sample_names"], p, ds
            ),
        )


def same_answer(got, want) -> str | None:
    """None when two per-dataset response lists agree exactly."""
    key = lambda r: (r.dataset_id, r.vcf_location)
    got = sorted(got, key=key)
    want = sorted(want, key=key)
    if [key(r) for r in got] != [key(r) for r in want]:
        return f"targets {[key(r) for r in got]} != {[key(r) for r in want]}"
    for g, w in zip(got, want):
        for f in (
            "exists",
            "call_count",
            "all_alleles_count",
            "variants",
            "sample_indices",
            "sample_names",
        ):
            if getattr(g, f) != getattr(w, f):
                a, b = getattr(g, f), getattr(w, f)
                if isinstance(a, list) and len(a) > 6:
                    a, b = f"{len(a)} items", f"{len(b)} items"
                return f"{g.dataset_id}.{f}: got {a!r}, want {b!r}"
    return None


def fold_answers(want: list, vcf_location: str):
    """The answers of a tail's delta shards as the ONE answer of the
    base they fold into: matches, calls and alleles add up. Sample hits
    are left out (which rows feed them depends on the whole result)."""
    from sbeacon_tpu.payloads import VariantSearchResponse

    return VariantSearchResponse(
        dataset_id=want[0].dataset_id,
        vcf_location=vcf_location,
        exists=any(w.exists for w in want),
        all_alleles_count=sum(w.all_alleles_count for w in want),
        call_count=sum(w.call_count for w in want),
        variants=sorted(v for w in want for v in w.variants),
    )


def folded_mismatch(got: list, want) -> str | None:
    if len(got) != 1 or got[0].vcf_location != want.vcf_location:
        return f"targets {[(r.dataset_id, r.vcf_location) for r in got]}"
    for f in ("exists", "call_count", "all_alleles_count"):
        if getattr(got[0], f) != getattr(want, f):
            return f"{f}: got {getattr(got[0], f)}, want {getattr(want, f)}"
    if sorted(got[0].variants) != want.variants:
        return f"variants differ ({len(got[0].variants)} rows)"
    return None


# -- the checks ---------------------------------------------------------------


class Check:
    """One variant request: its HTTP form, its engine payload, and what
    the envelope must say."""

    def __init__(self, name, path, datasets, *, chrom, start, end,
                 granularity, include="HIT", ref=None, alt=None, vtype=None,
                 min_len=None, max_len=None, filters=None, samples=None):
        self.name = name
        self.path = path
        self.datasets = list(datasets)
        self.chrom = chrom
        self.start = start  # (start_min, start_max), 1-based inclusive
        self.end = end  # (end_min, end_max)
        self.granularity = granularity
        self.include = include
        self.ref, self.alt, self.vtype = ref, alt, vtype
        self.min_len, self.max_len = min_len, max_len
        self.filters = filters
        self.samples = samples  # {dataset: [sample names]} when filtered

    def body(self) -> dict:
        rp = {
            "assemblyId": ASSEMBLY,
            "referenceName": self.chrom,
            # Beacon coordinates are 0-based; the two-element bracket
            # form says exactly which range is meant
            "start": [self.start[0] - 1, self.start[1] - 1],
            "end": [self.end[0] - 1, self.end[1] - 1],
        }
        for k, v in (
            ("referenceBases", self.ref),
            ("alternateBases", self.alt),
            ("variantType", self.vtype),
            ("variantMinLength", self.min_len),
            ("variantMaxLength", self.max_len),
        ):
            if v is not None:
                rp[k] = v
        query = {
            "requestedGranularity": self.granularity,
            "includeResultsetResponses": self.include,
            "requestParameters": rp,
            "pagination": {"skip": 0, "limit": 100_000},
        }
        if self.filters:
            query["filters"] = self.filters
        return {"query": query}

    def payload(self):
        from sbeacon_tpu.payloads import VariantQueryPayload

        return VariantQueryPayload(
            dataset_ids=self.datasets,
            reference_name=self.chrom,
            reference_bases=self.ref,
            alternate_bases=self.alt,
            start_min=self.start[0],
            start_max=self.start[1],
            end_min=self.end[0],
            end_max=self.end[1],
            variant_type=self.vtype,
            variant_min_length=self.min_len or 0,
            variant_max_length=-1 if self.max_len is None else self.max_len,
            requested_granularity=self.granularity,
            include_datasets=self.include,
            include_samples=True,
            sample_names=self.samples or {},
            selected_samples_only=bool(self.samples),
        )


BIG = 2**30


def row_window(shard, rng, n_rows: int):
    """(chrom, pos_lo, pos_hi, bracket rows) of ~n_rows consecutive rows
    inside one chromosome of the shard."""
    import numpy as np

    offs = shard.chrom_offsets
    codes = [c for c in range(len(offs) - 1) if offs[c + 1] - offs[c] > 8]
    code = rng.choice(codes)
    lo, hi = int(offs[code]), int(offs[code + 1])
    n_rows = min(n_rows, hi - lo - 1)
    a = rng.randrange(lo, hi - n_rows)
    pos = shard.cols["pos"]
    p_lo, p_hi = int(pos[a]), int(pos[a + n_rows - 1])
    seg = pos[lo:hi]
    width = int(
        np.searchsorted(seg, p_hi, side="right")
        - np.searchsorted(seg, p_lo, side="left")
    )
    return shard.row_chrom(a), p_lo, p_hi, width


def plain_row(shard, rng, lo: int = 0, hi: int | None = None) -> int:
    """A row with a plain (non-symbolic, non-'.') alt allele."""
    hi = shard.n_rows if hi is None else hi
    while True:
        r = rng.randrange(lo, hi)
        alt = shard.row_alt(r)
        if alt and not alt.startswith("<") and alt != ".":
            return r


def build_checks(shards: dict, index_ids, vcf_ds, vcf_records, rng):
    """The request list: boolean, count and record granularity over
    point, bracket and SV/indel shapes on the index corpus, selected
    samples on the plane dataset, queries spanning every dataset, and
    the freshly submitted one."""
    checks: list[Check] = []
    a_id = index_ids[0]
    a = shards[a_id]
    b = shards["kgp"]
    scoped = lambda ds: f"/datasets/{ds}/g_variants"

    # (a) point queries: exact ref/alt at a known row, three shapes
    for gran, include in (
        ("boolean", "NONE"),
        ("boolean", "HIT"),
        ("count", "HIT"),
        ("record", "HIT"),
        ("count", "ALL"),
        ("record", "ALL"),
    ):
        r = plain_row(a, rng)
        p = int(a.cols["pos"][r])
        checks.append(Check(
            f"a.point.{gran}.{include}", scoped(a_id), [a_id],
            chrom=a.row_chrom(r), start=(p, p), end=(1, BIG),
            granularity=gran, include=include,
            ref=a.row_ref(r), alt=a.row_alt(r),
        ))
    # ... and points that (almost surely) miss
    for gran in ("boolean", "count"):
        p = rng.randrange(1, 40_000_000)
        checks.append(Check(
            f"a.miss.{gran}", scoped(a_id), [a_id], chrom="3",
            start=(p, p), end=(1, BIG), granularity=gran, alt="T", ref="G",
        ))
    # (a) brackets: any single-base alt over ~n consecutive rows
    for gran, n in (("boolean", 12), ("count", 40), ("record", 40),
                    ("count", 400), ("record", 120)):
        chrom, lo, hi, _w = row_window(a, rng, n)
        checks.append(Check(
            f"a.bracket{n}.{gran}", scoped(a_id), [a_id], chrom=chrom,
            start=(lo, hi), end=(lo, BIG), granularity=gran, alt="N",
        ))
    # (a) a bracket wider than window_cap: by contract the uncapped
    # host matcher answers it, and the smoke counts exactly that
    chrom, lo, hi, _w = row_window(a, rng, 3000)
    checks.append(Check(
        "a.bracket3000.count", scoped(a_id), [a_id], chrom=chrom,
        start=(lo, hi), end=(lo, BIG), granularity="count", alt="N",
    ))
    # (a) SV / indel shapes: variantType with fuzzy bounds, lengths
    for vtype, gran in (("DEL", "count"), ("INS", "record"),
                        ("DUP", "count"), ("CNV", "boolean"),
                        ("DUP:TANDEM", "count")):
        chrom, lo, hi, _w = row_window(a, rng, 300)
        checks.append(Check(
            f"a.sv.{vtype}.{gran}", scoped(a_id), [a_id], chrom=chrom,
            start=(lo, hi), end=(lo, BIG), granularity=gran, vtype=vtype,
        ))
    chrom, lo, hi, _w = row_window(a, rng, 300)
    checks.append(Check(
        "a.indel.minmax.count", scoped(a_id), [a_id], chrom=chrom,
        start=(lo, hi), end=(lo, BIG), granularity="count", vtype="INS",
        min_len=3, max_len=12,
    ))
    chrom, lo, hi, _w = row_window(a, rng, 60)
    checks.append(Check(
        "a.refwild.record", scoped(a_id), [a_id], chrom=chrom,
        start=(lo, hi), end=(lo, BIG), granularity="record", ref="N",
        alt="N",
    ))

    # (b) selected samples: a filter on the individuals' sex selects
    # half the cohort of the plane dataset and nothing else
    names = b.meta["sample_names"]
    female = [s for i, s in enumerate(names) if i % 2 == 0]
    flt = [{"id": SEX_TERMS[0], "scope": "individuals"}]
    for gran, include in (("boolean", "NONE"), ("count", "HIT"),
                          ("record", "HIT"), ("record", "ALL")):
        r = plain_row(b, rng)
        p = int(b.cols["pos"][r])
        checks.append(Check(
            f"b.selected.point.{gran}.{include}", "/g_variants", ["kgp"],
            chrom=b.row_chrom(r), start=(p, p), end=(1, BIG),
            granularity=gran, include=include, ref=b.row_ref(r),
            alt=b.row_alt(r), filters=flt, samples={"kgp": female},
        ))
    for gran, n in (("count", 30), ("record", 30), ("boolean", 8),
                    ("record", 200)):
        chrom, lo, hi, _w = row_window(b, rng, n)
        checks.append(Check(
            f"b.selected.bracket{n}.{gran}", "/g_variants", ["kgp"],
            chrom=chrom, start=(lo, hi), end=(lo, BIG), granularity=gran,
            alt="N", filters=flt, samples={"kgp": female},
        ))
    # (b) sample extraction over the full cohort (no filter): the same
    # fused match+planes program under an all-ones mask
    for gran, n in (("record", 1), ("record", 25)):
        chrom, lo, hi, _w = row_window(b, rng, n)
        checks.append(Check(
            f"b.extract.bracket{n}.{gran}", scoped("kgp"), ["kgp"],
            chrom=chrom, start=(lo, hi), end=(lo, BIG), granularity=gran,
            alt="N",
        ))
    chrom, lo, hi, _w = row_window(b, rng, 150)
    checks.append(Check(
        "b.sv.DEL.count", scoped("kgp"), ["kgp"], chrom=chrom,
        start=(lo, hi), end=(lo, BIG), granularity="count", vtype="DEL",
    ))

    # every dataset at once: brackets on the submitted VCF's chromosome,
    # which every synthetic shard also covers
    every = sorted(list(shards) + [vcf_ds])

    def vcf_window(n: int):
        """(first, last) POS of n consecutive records of the VCF."""
        n = min(n, len(vcf_records) - 1)
        at = rng.randrange(0, len(vcf_records) - n)
        return vcf_records[at], vcf_records[at + n - 1]

    for gran, include, n in (
        ("boolean", "NONE", 6), ("boolean", "HIT", 6), ("count", "HIT", 30),
        ("count", "ALL", 30), ("record", "HIT", 30), ("count", "HIT", 90),
    ):
        lo, hi = vcf_window(n)
        checks.append(Check(
            f"all.bracket{n}.{gran}.{include}", "/g_variants", every,
            chrom=VCF_CHROM, start=(lo, hi), end=(lo, BIG),
            granularity=gran, include=include, alt="N",
        ))
    for gran in ("count", "boolean"):
        chrom, lo, hi, _w = row_window(a, rng, 40)
        checks.append(Check(
            f"all.other_chrom.{gran}", "/g_variants", every, chrom=chrom,
            start=(lo, hi), end=(lo, BIG), granularity=gran, alt="N",
        ))
    chrom, lo, hi, _w = row_window(a, rng, 200)
    checks.append(Check(
        "all.sv.DEL.count", "/g_variants", every, chrom=chrom,
        start=(lo, hi), end=(lo, BIG), granularity="count", vtype="DEL",
    ))

    # (c) the freshly submitted dataset
    for gran, include, n in (
        ("boolean", "HIT", 1), ("count", "HIT", 20), ("record", "HIT", 20),
        ("record", "ALL", 3), ("count", "HIT", 150),
    ):
        lo, hi = vcf_window(n)
        checks.append(Check(
            f"c.bracket{n}.{gran}.{include}", scoped(vcf_ds), [vcf_ds],
            chrom=VCF_CHROM, start=(lo, hi), end=(lo, BIG),
            granularity=gran, include=include, alt="N",
        ))
    vcf_names = [f"S{i}" for i in range(VCF_ENTITIES)]
    flt_c = [{"id": VCF_TERM, "scope": "individuals"}]
    for gran, n in (("count", 40), ("record", 40)):
        lo, hi = vcf_window(n)
        checks.append(Check(
            f"c.selected.bracket{n}.{gran}", "/g_variants", [vcf_ds],
            chrom=VCF_CHROM, start=(lo, hi), end=(lo, BIG),
            granularity=gran, alt="N", filters=flt_c,
            samples={vcf_ds: vcf_names},
        ))
    return checks


def build_tail_checks(deltas: list, rng) -> list[Check]:
    """Requests on the delta-tail dataset; every standing delta shard
    is a target of each one."""
    path = f"/datasets/{DELTA_DS}/g_variants"
    checks: list[Check] = []
    d0 = deltas[0]
    for gran in ("boolean", "record"):
        r = plain_row(d0, rng)
        p = int(d0.cols["pos"][r])
        checks.append(Check(
            f"d.point.{gran}", path, [DELTA_DS], chrom=d0.row_chrom(r),
            start=(p, p), end=(1, BIG), granularity=gran,
            ref=d0.row_ref(r), alt=d0.row_alt(r),
        ))
    for gran, include, n in (("count", "HIT", 12), ("record", "ALL", 30),
                             ("count", "ALL", 60)):
        chrom, lo, hi, _w = row_window(deltas[len(checks) % len(deltas)], rng, n)
        checks.append(Check(
            f"d.bracket{n}.{gran}", path, [DELTA_DS], chrom=chrom,
            start=(lo, hi), end=(lo, BIG), granularity=gran,
            include=include, alt="N",
        ))
    chrom, lo, hi, _w = row_window(deltas[-1], rng, 80)
    checks.append(Check(
        "d.sv.DEL.count", path, [DELTA_DS], chrom=chrom, start=(lo, hi),
        end=(lo, BIG), granularity="count", vtype="DEL",
    ))
    return checks


def vcf_positions(vcf: Path) -> list[int]:
    """POS of every record of the cohort VCF, in file order."""
    from sbeacon_tpu.genomics.bgzf import BgzfReader

    out = []
    for _, line in BgzfReader(vcf).iter_lines():
        if line and not line.startswith(b"#"):
            out.append(int(line.split(b"\t", 2)[1]))
    return out


class Client:
    def __init__(self, port: int, token: str):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
        self.token = token
        self.ok = 0
        self.failed = 0

    def request(self, method, path, body=None, *, auth=False, expect=200):
        headers = {"Content-Type": "application/json"}
        if auth:
            headers["Authorization"] = f"Bearer {self.token}"
        data = None if body is None else json.dumps(body).encode()
        self.conn.request(method, path, body=data, headers=headers)
        resp = self.conn.getresponse()
        raw = resp.read()
        doc = json.loads(raw) if raw[:1] in (b"{", b"[") else raw.decode()
        if resp.status == expect:
            self.ok += 1
        else:
            self.failed += 1
        return resp.status, doc

    def close(self):
        self.conn.close()


def envelope_facts(check: Check, want: list) -> dict:
    """What the Beacon envelope must carry for the reference answers
    (the route's aggregation: exists is an OR, the count is the number
    of distinct variants when resultset details were asked for)."""
    exists = any(r.exists for r in want)
    facts = {"exists": exists}
    if check.granularity == "boolean":
        return facts
    variants = set()
    if check.include in ("HIT", "ALL") and exists:
        # the route's accumulator only starts collecting once a
        # response has flipped exists, in (dataset, vcf) order
        seen = False
        for r in sorted(want, key=lambda r: (r.dataset_id, r.vcf_location)):
            seen = seen or r.exists
            if seen:
                variants.update(r.variants)
    facts["count"] = len(variants)
    if check.granularity == "record":
        facts["ids"] = sorted(
            base64.b64encode(
                "\t".join([ASSEMBLY, *v.split("\t")[:4]]).encode()
            ).decode()
            for v in variants
        )
    return facts


def envelope_mismatch(check: Check, doc: dict, facts: dict) -> str | None:
    summary = doc.get("responseSummary") or {}
    if bool(summary.get("exists")) != facts["exists"]:
        return f"exists {summary.get('exists')} != {facts['exists']}"
    if "count" in facts and summary.get("numTotalResults") != facts["count"]:
        return f"count {summary.get('numTotalResults')} != {facts['count']}"
    if "ids" in facts:
        got = sorted(
            r["variantInternalId"]
            for rs in doc["response"]["resultSets"]
            for r in rs["results"]
        )
        if got != facts["ids"]:
            return f"record ids differ ({len(got)} vs {len(facts['ids'])})"
    return None


def expected_host_rows(check: Check, shards: dict, eng_cfg) -> int:
    """Candidate rows the uncapped host matcher walks BY CONTRACT for
    this request: datasets whose bracket is wider than window_cap or
    whose matches exceed record_cap (the submitted dataset's checks
    stay far inside both)."""
    import numpy as np

    from sbeacon_tpu.engine import host_match_rows
    from sbeacon_tpu.utils.chrom import chromosome_code

    total = 0
    p = check.payload()
    for ds in check.datasets:
        shard = shards.get(ds)
        if shard is None or check.chrom not in shard.meta["chrom_native"]:
            continue
        code = chromosome_code(check.chrom)
        seg = shard.cols["pos"][
            int(shard.chrom_offsets[code]) : int(shard.chrom_offsets[code + 1])
        ]
        width = int(
            np.searchsorted(seg, check.start[1], side="right")
            - np.searchsorted(seg, check.start[0], side="left")
        )
        matched = len(
            host_match_rows(
                shard, query_spec(p), ref_wildcard=p.selected_samples_only
            )
        )
        if width > eng_cfg.window_cap or matched > eng_cfg.record_cap:
            total += width
    return total


# -- the run ------------------------------------------------------------------


class Run:
    """What the phases of one smoke run share."""

    def __init__(self, args, stage: Stages, summary: dict):
        self.args = args
        self.stage = stage
        self.summary = summary
        self.rng = random.Random(args.seed)
        self.token = f"smoke-{self.rng.getrandbits(64):016x}"
        self.vcf_ds = "kgvcf"
        self.scratch = Path(
            args.scratch or tempfile.mkdtemp(prefix="chip_smoke_")
        )
        self.scratch.mkdir(parents=True, exist_ok=True)
        # filled by the phases, in order
        self.shards: dict = {}
        self.index_ids: list[str] = []
        self.plane_ids: list[str] = []
        self.vcf: Path | None = None
        self.deltas: list = []
        self.config = None
        self.app = None
        self.engine = None
        self.server = None
        self.client: Client | None = None
        self.checks: list[Check] = []
        self.reference: Reference | None = None
        self.mid0 = 0  # mid-request compiles when warm_app returns
        self.parity = 0  # variant requests held to the reference

    @property
    def all_datasets(self) -> list[str]:
        return sorted(list(self.shards) + [self.vcf_ds])


def start_server(run: Run, platform: str) -> None:
    """The server, the way ``python -m sbeacon_tpu.api.server`` starts
    it: config from the environment, build_app (compile cache, app,
    persisted shards), the in-memory corpus pinned by the call load_all
    makes per shard, warm_app, then the HTTP server."""
    from sbeacon_tpu.api.server import build_app, start_background, warm_app
    from sbeacon_tpu.config import BeaconConfig
    from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
    from sbeacon_tpu.telemetry import flight_recorder

    if run.args.rehearsal and platform != "tpu":
        # a rehearsal drives the chip's index family on any backend;
        # make_device_index would pick the XLA family off the TPU
        import sbeacon_tpu.engine as engine_mod

        engine_mod.make_device_index = lambda shard, **kw: ScatterDeviceIndex(
            shard, device=kw.get("device")
        )

    # the one setting that is not a default: /submit wants a token
    os.environ["BEACON_SUBMIT_TOKEN"] = run.token
    run.config = BeaconConfig.from_env(run.scratch / "beacon_root")
    with run.stage("build_app"):
        run.app, n_loaded = build_app(run.config)
    run.engine = run.app.engine
    require(n_loaded == 0, "scratch data root was not empty")
    with run.stage("upload_index"):
        for ds in run.index_ids:
            run.engine.add_index(run.shards[ds])
    with run.stage("upload_planes"):
        for ds in run.plane_ids:
            run.engine.add_index(run.shards[ds])
    with run.stage("warmup"):
        run.summary["programs_warmed"] = warm_app(run.app)
    require(run.engine.warmup_failed_phases == 0, "a warmup phase failed")
    # from here on the server is up: whatever it publishes later (the
    # submitted VCF, the folded tail) it compiles before it serves it
    run.mid0 = flight_recorder.mid_request_compiles()
    run.server, _thread = start_background(run.app)
    run.client = Client(run.server.server_address[1], run.token)


def submit_everything(run: Run) -> None:
    """Metadata for the pinned datasets and the cohort VCF, over HTTP;
    then the device distinct count."""
    from sbeacon_tpu.parallel.distinct import distinct_count_device

    client, engine, vcf, vcf_ds = run.client, run.engine, run.vcf, run.vcf_ds

    def submit(name, body):
        st, doc = client.request("POST", "/submit", body, auth=True)
        require(st == 200, f"/submit {name}: {st} {doc}")
        return doc

    with run.stage("submit_metadata"):
        for ds in run.index_ids + run.plane_ids[1:]:
            submit(ds, metadata_submission(ds, [], None))
        submit(
            "kgp",
            metadata_submission(
                "kgp", run.shards["kgp"].meta["sample_names"],
                lambda i: "HP:0000118",
            ),
        )
    st, doc = client.request(
        "POST", "/submit", metadata_submission(vcf_ds, [], None, [vcf]),
        expect=401,
    )
    require(st == 401, f"/submit without the token answered {st}")
    with run.stage("submit_vcf"):
        doc = submit(
            vcf_ds,
            metadata_submission(
                vcf_ds, [f"S{i}" for i in range(VCF_ENTITIES)],
                lambda i: VCF_TERM, [vcf],
            ),
        )
    require(
        any("Summarised" in m for m in doc["pending"]),
        f"/submit did not summarise: {doc}",
    )
    run.summary["submit"] = doc["pending"]
    require(
        engine.has_index(vcf_ds, str(vcf)),
        "the submitted VCF has no base shard",
    )
    require(
        engine.delta_depth(vcf_ds, str(vcf)) == 0,
        "the submitted VCF still has a delta tail",
    )

    require(engine.warmup_failed_phases == 0, "a warm phase failed")

    # the device distinct count against /submit's own (host) count
    with run.stage("distinct_device"):
        n_distinct = distinct_count_device(
            [engine.export_artifact(vcf_ds, str(vcf))]
        )
    require(
        f"{n_distinct} distinct variants" in doc["pending"][0],
        f"device distinct count {n_distinct} != {doc['pending'][0]}",
    )
    run.summary["distinct_variants"] = n_distinct


def run_check(run: Run, c: Check, want: list) -> None:
    """One variant request over HTTP and its engine twin, held to the
    reference."""
    st, doc = run.client.request("POST", c.path, c.body())
    require(st == 200, f"{c.name}: HTTP {st} {doc}")
    diff = envelope_mismatch(c, doc, envelope_facts(c, want))
    require(diff is None, f"{c.name}: envelope {diff}")
    # the envelope carries exists, count and ids; call and allele
    # counts and sample hits are read off the engine, past the
    # response cache, so the device answers again
    live = dataclasses.replace(c.payload(), no_response_cache=True)
    diff = same_answer(run.engine.search(live), want)
    require(diff is None, f"{c.name}: {diff}")
    run.parity += 1


def check_variant_answers(run: Run) -> int:
    """Every variant request against the reference; returns the host
    matcher rows the overflow contract explains."""
    with run.stage("reference"):
        run.checks = build_checks(
            run.shards, run.index_ids, run.vcf_ds, vcf_positions(run.vcf),
            run.rng,
        )
        run.reference = Reference(
            run.shards, run.vcf_ds, run.vcf,
            [f"S{i}" for i in range(N_SAMPLES)],
        )
        wanted = [run.reference.responses(c.payload()) for c in run.checks]
        host_rows = sum(
            expected_host_rows(c, run.shards, run.config.engine)
            for c in run.checks
        )
    with run.stage("queries"):
        for c, want in zip(run.checks, wanted):
            run_check(run, c, want)
    return host_rows


def check_delta_tail(run: Run) -> None:
    """(d): a delta tail, published the way the streaming ingest
    publishes each finished slice (``engine.add_delta``, the call of
    ``ingest/pipeline.py`` ``publish_delta``). It is queried while it
    stands, when the L0 index answers, and again after the compactor
    has folded it into a base on the serving engine."""
    engine, client, deltas = run.engine, run.client, run.deltas
    compactor = run.app.ingest.compactor
    key = (DELTA_DS, deltas[0].meta["vcf_location"])
    st, doc = client.request(
        "POST", "/submit", metadata_submission(DELTA_DS, [], None), auth=True
    )
    require(st == 200, f"/submit {DELTA_DS}: {st} {doc}")
    checks = build_tail_checks(deltas, run.rng)
    # the background sweep folds every standing tail when its interval
    # comes round: hold it while the tail is read, then fold on purpose
    compactor.close()
    try:
        with run.stage("delta_publish"):
            epochs = [engine.add_delta(s) for s in deltas]
        require(
            engine.delta_depth(*key) == DELTA_SHARDS,
            f"{engine.delta_depth(*key)} delta shards stand",
        )
        l0 = engine.l0_status()
        require(
            l0["built"] and l0["shards"] == DELTA_SHARDS,
            f"the standing tail has no L0 index: {l0}",
        )
        run.reference.tails[DELTA_DS] = [
            (f"{key[1]}#d{e}", s) for e, s in zip(epochs, deltas)
        ]
        with run.stage("delta_queries"):
            wanted = [run.reference.responses(c.payload()) for c in checks]
            for c, want in zip(checks, wanted):
                run_check(run, c, want)
        l0_served = engine.l0_status()["servedQueries"] - l0["servedQueries"]
        require(
            l0_served >= len(checks),
            f"the L0 index served {l0_served} of {len(checks)} tail queries",
        )
    finally:
        run.reference.tails.pop(DELTA_DS, None)
        compactor.start()
    with run.stage("delta_fold"):
        folded = compactor.run_once(key)
    rows = sum(s.n_rows for s in deltas)
    require(folded == {key: rows}, f"the compactor folded {folded}")
    require(
        engine.delta_depth(*key) == 0 and engine.has_index(*key),
        "the folded tail is not a base",
    )
    with run.stage("delta_folded_queries"):
        for c, per_shard in zip(checks, wanted):
            want = fold_answers(per_shard, key[1])
            st, doc = client.request("POST", c.path, c.body())
            require(st == 200, f"{c.name} folded: HTTP {st} {doc}")
            diff = envelope_mismatch(c, doc, envelope_facts(c, [want]))
            require(diff is None, f"{c.name} folded: envelope {diff}")
            live = dataclasses.replace(c.payload(), no_response_cache=True)
            diff = folded_mismatch(engine.search(live), want)
            require(diff is None, f"{c.name} folded: {diff}")
    run.summary["delta_tail"] = {
        "shards": DELTA_SHARDS,
        "rows": rows,
        "parity_standing": f"{len(checks)}/{len(checks)}",
        "l0_served_queries": l0_served,
        "parity_folded": f"{len(checks)}/{len(checks)}",
    }


def check_other_routes(run: Run) -> None:
    """A variant by id and the individuals carrying it, /datasets, an
    entity route with a filter, the probes."""
    client, b = run.client, run.shards["kgp"]
    r = plain_row(b, run.rng)
    pos = int(b.cols["pos"][r])
    fields = [ASSEMBLY, b.row_chrom(r), str(pos), b.row_ref(r), b.row_alt(r)]
    vid = base64.b64encode("\t".join(fields).encode()).decode()
    by_id = Check(
        "by_id", "", run.all_datasets, chrom=b.row_chrom(r),
        start=(pos, pos), end=(pos, pos + len(b.row_alt(r))),
        granularity="record", include="ALL", ref=b.row_ref(r),
        alt=b.row_alt(r),
    )
    want = run.reference.responses(by_id.payload())
    st, doc = client.request(
        "GET", f"/g_variants/{vid}?requestedGranularity=record"
    )
    require(st == 200, f"/g_variants/{{id}}: {st}")
    diff = envelope_mismatch(by_id, doc, envelope_facts(by_id, want))
    require(diff is None, f"/g_variants/{{id}}: {diff}")
    carriers = sum(
        len(w.sample_names) for w in want if w.dataset_id == "kgp"
    )
    st, doc = client.request(
        "GET", f"/g_variants/{vid}/individuals?requestedGranularity=count"
    )
    require(st == 200, f"/g_variants/{{id}}/individuals: {st}")
    require(
        doc["responseSummary"]["numTotalResults"] == carriers > 0,
        f"/g_variants/{{id}}/individuals: {doc['responseSummary']} "
        f"!= {carriers} carriers",
    )
    st, doc = client.request(
        "GET", "/datasets?requestedGranularity=record&limit=100"
    )
    require(st == 200, f"/datasets: {st} {doc}")
    got = sorted(d["id"] for d in doc["response"]["resultSets"][0]["results"])
    require(got == run.all_datasets, f"/datasets: {got}")
    st, doc = client.request(
        "POST", "/individuals",
        {"query": {"requestedGranularity": "count",
                   "filters": [{"id": SEX_TERMS[0]}]}},
    )
    females = -(-N_SAMPLES // 2) + -(-VCF_ENTITIES // 2)
    require(
        st == 200 and doc["responseSummary"]["numTotalResults"] == females,
        f"/individuals filter: {st} {doc.get('responseSummary')}",
    )
    st, doc = client.request("GET", "/health")
    require(st == 200 and doc["ok"], f"/health: {st}")
    st, doc = client.request("GET", "/ready")
    require(
        st == 200 and doc["shards"] == len(run.all_datasets),
        f"/ready: {st} {doc}",
    )


def check_server_surfaces(run: Run, n_dev: int, host_rows_expected: int):
    """What the server says of itself: launches by family, fallbacks,
    compiles after warmup, the canary, the host matcher's share."""
    from sbeacon_tpu.accounting import SYSTEM_TENANT
    from sbeacon_tpu.telemetry import flight_recorder

    summary, client = run.summary, run.client
    st, metrics = client.request("GET", "/metrics")
    require(st == 200, f"/metrics: {st}")
    st, status = client.request("GET", "/device/status")
    require(st == 200, f"/device/status: {st}")
    launches = metrics["device"]["launches"]
    fallbacks = {
        "device.fallbacks": metrics["device"]["fallbacks"],
        "ingest.native_fallbacks": metrics["ingest"]["native_fallbacks"],
    }
    summary["launches"] = launches
    summary["fallbacks"] = fallbacks
    summary["engine"] = {
        "fused_searches": metrics["engine"]["fused_searches"],
        "mesh_searches": metrics["engine"]["mesh_searches"],
        "l0_builds": metrics["ingest"]["l0_builds"],
        "l0_served_queries": metrics["ingest"]["l0_served_queries"],
    }
    require(
        metrics["ingest"]["l0_builds"] > 0
        and metrics["ingest"]["l0_served_queries"] > 0,
        f"the L0 index built or served nothing: {summary['engine']}",
    )
    require(
        status["fallbacks"] == metrics["device"]["fallbacks"],
        "/device/status and /metrics disagree on fallbacks",
    )
    require(not any(fallbacks.values()), f"fallbacks counted: {fallbacks}")
    for family in ("scatter", "plane", "fused_l0"):
        require(launches.get(family, 0) > 0, f"no {family} launch recorded")
    if n_dev == 1:
        require(launches.get("fused", 0) > 0, "no fused launch recorded")
        require(
            metrics["engine"]["fused_searches"] > 0,
            "no multi-dataset query rode the fused stack",
        )
    else:
        require(
            metrics["engine"]["mesh_searches"] > 0,
            "no multi-dataset query rode the mesh program",
        )
    summary["mid_request_compiles"] = metrics["device"]["mid_request_compiles"]
    summary["mid_request_compiles_after_warmup"] = (
        flight_recorder.mid_request_compiles() - run.mid0
    )
    require(
        flight_recorder.mid_request_compiles() == run.mid0,
        "a program compiled inside a request after warmup: "
        f"{flight_recorder.last_mid_request_compile()}",
    )
    require(
        metrics["shaping"]["brownout_level"] == 0,
        f"the server browned out: level "
        f"{metrics['shaping']['brownout_level']}, SLO breached on "
        f"{[r for r, b in metrics['slo']['breached'].items() if b]}",
    )
    canary = metrics["canary"]
    require(
        not canary["mismatches"] and not canary["failures"],
        f"canary: {canary}",
    )
    # request tenants only: the compactor books the rows it folds
    # under the "system" tenant of the same series
    host_rows = sum(
        v for t, v in metrics["cost"]["host_rows"].items()
        if t != SYSTEM_TENANT
    )
    summary["host_matcher_rows"] = {
        "counted": host_rows, "by_contract": host_rows_expected,
    }
    require(
        host_rows == host_rows_expected,
        f"host matcher walked {host_rows} rows, the overflow contract "
        f"explains {host_rows_expected}",
    )
    summary["requests"] = {"ok": client.ok, "failed": client.failed}
    require(client.failed == 0, f"{client.failed} requests failed")
    return status


def check_device_memory(run: Run, devices, status: dict) -> None:
    """Every loaded index is the chip's family with its planes
    resident on its owner chip, each device holds at least what the
    engine reports there, and on several chips they hold alike."""
    from sbeacon_tpu.ops.plane_kernel import PlaneDeviceIndex
    from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex

    engine = run.engine
    index_bytes = plane_bytes = plane_gate_bytes = 0
    owner_of = {
        (row["dataset"], row["vcf"]): row["chip"]
        for row in engine.placement_table()
    }
    for key, shard, planes in engine.index_snapshot():
        dindex = engine._indexes[key][1]
        require(
            isinstance(dindex, ScatterDeviceIndex),
            f"{key}: index is {type(dindex).__name__}",
        )
        for a in [dindex.tiles] + (planes.planes() if planes else []):
            require(
                {d.id for d in a.devices()} == {owner_of[key]},
                f"{key}: an array lies on {a.devices()}, its owner is "
                f"chip {owner_of[key]}",
            )
        index_bytes += dindex.nbytes()
        if shard.gt_bits is not None:
            require(
                isinstance(planes, PlaneDeviceIndex),
                f"{key}: genotype planes are not on the device",
            )
            # the arrays' own size, held in whole 128-lane rows, and
            # what the budget gate reserved for them
            plane_bytes += planes.nbytes_hbm()
            plane_gate_bytes += PlaneDeviceIndex.estimate_hbm(shard)
    fused = engine._fused_state
    fused_bytes = (
        sum(int(a.size) * a.dtype.itemsize for a in fused[0].arrays.values())
        if fused is not None
        else 0
    )
    hbm = []
    for d in devices:
        ms = d.memory_stats() or {}
        hbm.append(
            {
                "id": d.id,
                "bytes_in_use": ms.get("bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit"),
            }
        )
    run.summary["hbm"] = hbm
    run.summary["placement"] = engine.placement_table()
    # what the engine holds by chip (tiles and planes on their owners,
    # the mesh stack's slice), and what lies on the default device alone
    by_chip: dict[int, int] = {}
    for (chip, _kind), nbytes in engine.resident_bytes().items():
        by_chip[int(chip)] = by_chip.get(int(chip), 0) + nbytes
    by_chip[devices[0].id] = by_chip.get(devices[0].id, 0) + fused_bytes
    run.summary["resident_bytes"] = {
        "by_chip": {str(c): n for c, n in sorted(by_chip.items())},
        "tiles": index_bytes, "planes": plane_bytes,
        "planes_by_ledger": status["hbm"]["residentBytes"],
        "fused_stack_on_device_0": fused_bytes,
    }
    print(
        f"[smoke] placement {json.dumps(run.summary['placement'])}; "
        f"bytes_in_use {[h['bytes_in_use'] for h in hbm]}; the engine's "
        f"own count by chip {run.summary['resident_bytes']['by_chip']}",
        file=sys.stderr, flush=True,
    )
    require(
        status["hbm"]["residentBytes"] == plane_bytes,
        "the plane ledger and the plane indexes disagree",
    )
    require(
        plane_gate_bytes == plane_bytes,
        f"the planes hold {plane_bytes} B, the budget gate reserved "
        f"{plane_gate_bytes} B",
    )
    if devices[0].platform != "tpu":
        return  # the CPU backend reports no memory statistics
    for h in hbm:
        require(
            h["bytes_in_use"] >= by_chip.get(h["id"], 0),
            f"device {h['id']} holds {h['bytes_in_use']} B, the engine "
            f"reports {by_chip.get(h['id'], 0)} B resident there",
        )
    if len(devices) > 1:
        # a plane dataset and one or two index datasets a chip. The
        # fused stack lies whole on the default device, placed by
        # nobody (PERF.md 7): it is taken off that chip's reading, so
        # what has to be alike is what the placement governs
        used = [
            h["bytes_in_use"]
            - (fused_bytes if h["id"] == devices[0].id else 0)
            for h in hbm
        ]
        require(
            min(used) >= 0.85 * max(used),
            "the chips do not hold alike: bytes_in_use less the fused "
            f"stack on device {devices[0].id} {used}",
        )
    budget = run.config.engine.plane_hbm_budget_gb * 1e9
    for h in hbm:
        require(
            h["bytes_limit"] >= budget,
            f"device {h['id']}: bytes_limit {h['bytes_limit']} is under "
            "plane_hbm_budget_gb",
        )
        require(h["bytes_in_use"] > 0, f"device {h['id']} holds nothing")


def check_plane_programs(run: Run, devices) -> None:
    """The programs that gather plane rows hold no copy of a plane: the
    planes are resident in the layout the gather reads (PERF.md, PR 25;
    resident ``[n, 79]`` every launch re-tiled the whole plane first).
    Then the compile PR 23 saw refused, 2e7 rows with planes: reported,
    not required."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sbeacon_tpu.ops.plane_kernel import _plane_stats, resident_shape
    from sbeacon_tpu.ops.query_pack import N_QWORDS
    from sbeacon_tpu.ops.scatter_kernel import (
        ScatterDeviceIndex,
        _selected_batch,
        _static_seg_k,
    )

    on_dev0 = SingleDeviceSharding(devices[0])
    key, _shard, planes = max(
        (e for e in run.engine.index_snapshot() if e[2] is not None),
        key=lambda e: e[2].n_rows,
    )
    sindex = run.engine._indexes[key][1]
    tile, n_words = sindex.tile, planes.n_words
    plane_bytes = int(planes.gt.nbytes)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=on_dev0)

    def selected(n_rows, seg_k):
        # a launch group of one dataset: a slot over its own buffers
        n_tiles = n_rows // tile + 1 + ScatterDeviceIndex.MAX_C
        plane = shape(*resident_shape(n_rows, n_words))
        return _selected_batch.lower(
            (shape(n_tiles, 8, tile),), ((plane,),),
            shape(1, 1 + N_QWORDS + n_words),
            T=tile, CAP=tile, C=1, exact_only=True, R=tile, seg_k=seg_k,
        ).compile().memory_analysis()

    plane = shape(*planes.gt.shape)
    temps = {
        "_selected_batch": selected(planes.n_rows, _static_seg_k(sindex)),
        "_plane_stats": _plane_stats.lower(
            plane, plane, plane, plane, shape(1024), shape(), shape(1024),
            shape(n_words), R=1024, with_counts=False, with_or=True,
        ).compile().memory_analysis(),
    }
    temps = {k: int(m.temp_size_in_bytes) for k, m in temps.items()}
    report = {"plane_bytes": plane_bytes, "temp_bytes": temps}
    if not run.args.rehearsal:  # a toy plane is smaller than a launch's rows
        for name, temp in temps.items():
            require(
                temp < plane_bytes // 8,
                f"{name} holds {temp} B of temp beside a plane of "
                f"{plane_bytes} B: the whole-plane copy is back",
            )
    try:
        big = selected(20_000_000, 2)
        report["compile_2e7_rows"] = {
            "compiled": True,
            "temp_bytes": int(big.temp_size_in_bytes),
            "argument_bytes": int(big.argument_size_in_bytes),
        }
    except Exception as e:  # the compiler's refusal is the finding
        report["compile_2e7_rows"] = {
            "compiled": False, "error": str(e).splitlines()[0][:300],
        }
    run.summary["plane_programs"] = report
    print(json.dumps({"plane_programs": report}), file=sys.stderr, flush=True)


def shut_down(run: Run) -> None:
    with run.stage("shutdown"):
        if run.client is not None:
            run.client.close()
        if run.server is not None:
            run.server.shutdown()
            run.server.server_close()
        if run.app is not None:
            run.app.close()
            run.engine.close()
        if not run.args.scratch:
            shutil.rmtree(run.scratch, ignore_errors=True)


def run_smoke(args, stage: Stages) -> dict:
    import jax

    from sbeacon_tpu import native
    from sbeacon_tpu.config import enable_persistent_compile_cache

    sizes = REHEARSAL if args.rehearsal else FULL
    devices = jax.devices()
    platform, n_dev = devices[0].platform, len(devices)
    summary: dict = {
        "ok": False,
        "device": {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": n_dev,
        },
        "jax": jax.__version__,
        "rehearsal": args.rehearsal,
        "seed": args.seed,
        "note": "set-up times and counts, not a benchmark",
    }
    run = Run(args, stage, summary)

    with stage("native_build"):
        lib = native.build(force=True)
    require(native.available(), "native library did not build/load")
    summary["native_lib"] = lib.name

    cache_dir = enable_persistent_compile_cache()
    cache_dir.mkdir(parents=True, exist_ok=True)
    require(
        str(jax.config.jax_compilation_cache_dir) == str(cache_dir),
        f"compile cache not at {cache_dir}",
    )
    summary["compile_cache"] = {
        "dir": str(cache_dir),
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": cache_entries(cache_dir),
    }

    try:
        run.shards, run.vcf, vcf_stats, run.deltas = make_datasets(
            sizes, args.seed, n_dev, run.scratch, stage
        )
        run.index_ids = sorted(d for d in run.shards if d.startswith("kg-"))
        run.plane_ids = ["kgp"] + sorted(
            d for d in run.shards if d.startswith("kgq-")
        )
        summary["sizes"] = {
            "n_samples": N_SAMPLES,
            "index_datasets": len(run.index_ids),
            "index_rows": sum(run.shards[d].n_rows for d in run.index_ids),
            "plane_datasets": len(run.plane_ids),
            "plane_rows": run.shards["kgp"].n_rows,
            "plane_rows_note": "a tenth of the index rows; widths are not cut",
            "vcf_records": vcf_stats["records"],
            "vcf_bytes_raw": vcf_stats["bytes_raw"],
            "delta_tail_rows": sum(s.n_rows for s in run.deltas),
        }
        start_server(run, platform)
        submit_everything(run)
        host_rows = check_variant_answers(run)
        with stage("routes"):
            check_other_routes(run)
        check_delta_tail(run)
        summary["parity"] = f"{run.parity}/{run.parity}"
        status = check_server_surfaces(run, n_dev, host_rows)
        check_device_memory(run, devices, status)
        with stage("plane_programs"):
            check_plane_programs(run, devices)
    finally:
        shut_down(run)

    summary["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    summary["seconds"] = stage.seconds
    summary["rehearsal_passed"] = args.rehearsal
    summary["ok"] = not args.rehearsal and platform == "tpu"
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument(
        "--rehearsal",
        action="store_true",
        help="toy sizes on whatever platform JAX finds; names the "
        "platform and never reports ok",
    )
    ap.add_argument(
        "--scratch",
        default=None,
        help="directory for generated data (default: a fresh temporary "
        "directory, removed at the end)",
    )
    ap.add_argument(
        "--out",
        default=str(DEFAULT_OUT),
        help="directory the summary is also written to as a file "
        "(default: chiprun_out/ beside this script)",
    )
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    try:
        import jax

        import sbeacon_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: cannot import the system: {e}", file=sys.stderr)
        return 1
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearsal:
        print(
            f"chip_smoke: JAX found no TPU (platform {platform!r}); the "
            "smoke runs on the chip only. --rehearsal runs toy sizes here.",
            file=sys.stderr,
        )
        return 1

    stage = Stages()
    summary = run_smoke(args, stage)
    summary["seconds"]["total"] = round(time.perf_counter() - t0, 1)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    name = "chip_smoke_rehearsal.json" if args.rehearsal else "chip_smoke.json"
    (out_dir / name).write_text(json.dumps(summary, indent=1) + "\n")
    # two lines: what the run saw, then the verdict alone. The last line
    # of stdout is exactly {"ok", "device"}; whoever reads it reads only that
    print(json.dumps(summary))
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
