"""Typed query/response contracts.

These are the framework's internal equivalents of the reference's
lambda-to-lambda message types (reference: shared_resources/payloads/
lambda_payloads.py:8-77 SplitQueryPayload/PerformQueryPayload and
lambda_responses.py:15-24 PerformQueryResponse). In the reference they cross
SNS/invoke process boundaries as JSON; here they cross the host->engine
boundary (and the DCN boundary between an API host and TPU workers), so they
stay dataclasses with a stable dict form.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field


def fields_of(obj) -> dict:
    """A payload's fields as they are, for ``json.dumps``: every value
    is already a JSON type, and ``dataclasses.asdict`` would copy each
    container element by element in Python first (a selection or a
    carrier list is 18,191 names at biobank width)."""
    return {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}


@dataclass
class VariantQueryPayload:
    """One variant search against one-or-more datasets.

    Coordinates are **1-based inclusive**, already converted from Beacon's
    0-based request form (the +1 dance at reference variantutils/
    search_variants.py:65-68 happens in the API layer before this payload is
    built).
    """

    dataset_ids: list[str] = field(default_factory=list)
    reference_name: str = ""  # canonical chromosome, e.g. "22"
    reference_bases: str | None = None
    alternate_bases: str | None = None
    start_min: int = 0
    start_max: int = 0
    end_min: int = 0
    end_max: int = 0
    variant_type: str | None = None
    variant_min_length: int = 0
    variant_max_length: int = -1  # -1 = unbounded
    requested_granularity: str = "boolean"
    include_datasets: str = "NONE"  # NONE/HIT/MISS/ALL
    include_samples: bool = False
    sample_names: dict[str, list[str]] = field(default_factory=dict)
    # restrict to these samples per dataset (selected-samples path)
    selected_samples_only: bool = False
    # bypass the response cache (ISSUE 12): known-answer canary probes
    # must observe the LIVE data plane — a warm cached answer would
    # mask exactly the silent corruption they exist to catch. Normal
    # traffic never sets this.
    no_response_cache: bool = False
    query_id: str = "TEST"

    @property
    def include_details(self) -> bool:
        # reference splitQuery: check_all = include_datasets in (HIT, ALL)
        return self.include_datasets in ("HIT", "ALL")

    def dumps(self) -> str:
        d = fields_of(self)
        # wire compat: the probe-only flag rides the wire ONLY when set
        # — a default-False field in every /search body would break a
        # not-yet-upgraded worker mid rolling deploy (its constructor
        # rejects unknown keywords)
        if not d.get("no_response_cache"):
            d.pop("no_response_cache", None)
        return json.dumps(d)

    @staticmethod
    def from_doc(doc: dict) -> "VariantQueryPayload":
        """Build from a wire dict, DROPPING unknown keys: a worker must
        keep answering coordinators one payload-field ahead of it (the
        forward half of the rolling-deploy contract; ``dumps`` omitting
        default-valued new fields is the backward half). A non-empty
        doc with NO known field at all is malformed, not newer — it
        still raises, so garbage POSTs keep surfacing as worker errors
        instead of parsing into an empty default query."""
        known = {
            f.name for f in dataclasses.fields(VariantQueryPayload)
        }
        kept = {k: v for k, v in doc.items() if k in known}
        if doc and not kept:
            raise ValueError(
                "payload has no known fields: "
                + ", ".join(sorted(doc))
            )
        return VariantQueryPayload(**kept)

    @staticmethod
    def loads(s: str) -> "VariantQueryPayload":
        return VariantQueryPayload.from_doc(json.loads(s))


@dataclass
class VariantSearchResponse:
    """Per-(dataset, vcf) search result.

    Field-compatible with the reference's PerformQueryResponse
    (lambda_responses.py:15-24): ``variants`` entries are the same
    tab-joined '{chrom}\\t{pos}\\t{ref}\\t{alt}\\t{vt}' strings the route
    aggregation layer parses back (reference: getGenomicVariants/
    route_g_variants.py:162-171).
    """

    dataset_id: str = ""
    vcf_location: str = ""
    exists: bool = False
    all_alleles_count: int = 0
    call_count: int = 0
    variants: list[str] = field(default_factory=list)
    sample_indices: list[int] = field(default_factory=list)
    sample_names: list[str] = field(default_factory=list)

    def dumps(self) -> str:
        return json.dumps(fields_of(self))

    @staticmethod
    def loads(s: str) -> "VariantSearchResponse":
        return VariantSearchResponse(**json.loads(s))


@dataclass
class SliceScanPayload:
    """One ingest slice-scan job for a remote worker.

    The reference fans each VCF's virtual-offset slices to <=1000
    summariseSlice lambdas over SNS (reference: summariseVcf/
    lambda_function.py:217-229 publish_slice_updates; summariseSlice/
    main.cpp:440-467). Here the same unit of work crosses the worker HTTP
    boundary: the worker range-reads [vstart, vend) of ``vcf_location``
    (local shared path or object-store URL), builds the slice's index
    shard, and returns it as one npz blob (columnar.dumps_index)."""

    dataset_id: str = ""
    vcf_location: str = ""
    vstart: int = 0
    vend: int = 0
    sample_names: list[str] = field(default_factory=list)

    def dumps(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def loads(s: str) -> "SliceScanPayload":
        return SliceScanPayload(**json.loads(s))
