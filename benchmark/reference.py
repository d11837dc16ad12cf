"""The plain reference: what a beacon must answer, from the corpus columns.

Copied from ``engine.host_match_rows`` / ``engine.materialize_response_loop``
/ ``chip_smoke.Reference`` and the route's aggregation, so that no later PR
can move it. It imports nothing of the program: it reads the corpus arrays
(the data every run makes from ``--seed``) and the request body as it was
sent over HTTP, and answers with the uncapped numpy matcher and a per-record
Python loop. No kernel, cache, batch or cap.
"""

from __future__ import annotations

import base64
from dataclasses import dataclass, field

import numpy as np

# allele-flag bits of the corpus' ``flags`` column (index/columnar.py FLAG)
SYMBOLIC = 1
CN_PREFIX = 2
CN0 = 4
CN1 = 8
CN2 = 16
DOT = 32
DEL_PREFIX = 64
DUP_PREFIX = 128
SINGLE_BASE = 256
AC_INFO = 512
AN_INFO = 1024

_UPPER = np.arange(256, dtype=np.uint8)
_UPPER[97:123] -= 32

#: canonical chromosome -> the code that indexes ``chrom_offsets``
CHROM_CODES = {str(i): i for i in range(1, 23)} | {"X": 23, "Y": 24, "MT": 25}


def chrom_code(name: str) -> int:
    name = str(name)
    if name.lower().startswith("chr"):
        name = name[3:]
    return CHROM_CODES.get(name.upper(), 0)


@dataclass
class RefShard:
    """The columns of one dataset the reference reads."""

    dataset_id: str
    vcf_location: str
    cols: dict
    chrom_offsets: np.ndarray
    ref_blob: np.ndarray
    ref_off: np.ndarray
    alt_blob: np.ndarray
    alt_off: np.ndarray
    sample_names: list = field(default_factory=list)
    gt_bits: np.ndarray | None = None
    vt_label: str = "N/A"

    @classmethod
    def of(cls, shard) -> "RefShard":
        """From the program's shard object: arrays are shared, not copied
        (25 GB of planes at full size)."""
        return cls(
            dataset_id=shard.meta["dataset_id"],
            vcf_location=shard.meta["vcf_location"],
            cols=shard.cols,
            chrom_offsets=shard.chrom_offsets,
            ref_blob=shard.ref_blob,
            ref_off=shard.ref_off,
            alt_blob=shard.alt_blob,
            alt_off=shard.alt_off,
            sample_names=list(shard.meta.get("sample_names") or []),
            gt_bits=shard.gt_bits,
            vt_label=shard.meta["vt_vocab"][0],
        )

    def ref(self, i: int) -> str:
        return bytes(self.ref_blob[self.ref_off[i] : self.ref_off[i + 1]]).decode()

    def alt(self, i: int) -> str:
        return bytes(self.alt_blob[self.alt_off[i] : self.alt_off[i + 1]]).decode()


@dataclass
class Query:
    """One /g_variants request, parsed from the body that was sent."""

    chrom: str
    start_min: int
    start_max: int
    end_min: int
    end_max: int
    ref: str | None
    alt: str | None
    vtype: str | None
    min_len: int
    max_len: int
    granularity: str
    include: str
    skip: int
    limit: int
    filter_ids: list
    assembly: str


def parse_body(body: dict) -> Query:
    """Beacon v2 POST body -> Query. The 0-based start/end lists become
    1-based inclusive brackets: two elements are a bracket; one start with
    one end is a start-anchored range whose end bounds the variant end."""
    q = body["query"]
    rp = q["requestParameters"]
    start, end = list(rp["start"]), list(rp["end"])
    if len(start) == 2:
        start_min, start_max = start
    else:
        start_min = start[0]
    if len(end) == 2:
        end_min, end_max = end
    else:
        end_min, end_max = start_min, end[0]
    if len(start) != 2:
        start_max = end_max
    up = lambda v: v.upper() if isinstance(v, str) else v
    page = q.get("pagination") or {}
    return Query(
        chrom=str(rp["referenceName"]),
        start_min=start_min + 1,
        start_max=start_max + 1,
        end_min=end_min + 1,
        end_max=end_max + 1,
        ref=up(rp.get("referenceBases")),
        alt=up(rp.get("alternateBases")),
        vtype=up(rp.get("variantType")),
        min_len=int(rp.get("variantMinLength", 0)),
        max_len=int(rp.get("variantMaxLength", -1)),
        granularity=q.get("requestedGranularity", "boolean"),
        include=q.get("includeResultsetResponses", "NONE"),
        skip=int(page.get("skip", 0)),
        limit=int(page.get("limit", 100)),
        filter_ids=[f["id"] if isinstance(f, dict) else f for f in q.get("filters") or []],
        assembly=rp["assemblyId"],
    )


def _blob_eq(blob, off, idx, lens, want: bytes, *, upper, prefix=False, wildcard_n=False):
    """Per-row compare of blob slices against one string: equality, prefix,
    or equality where an 'N' in ``want`` accepts any of A/C/G/T/N."""
    wlen = len(want)
    out = np.zeros(len(idx), dtype=bool)
    cand = lens >= wlen if prefix else lens == wlen
    if wlen == 0:
        out[:] = True if prefix else lens == 0
        return out
    if not cand.any():
        return out
    starts = off[idx[cand]].astype(np.int64)
    mat = blob[starts[:, None] + np.arange(wlen)]
    if upper:
        mat = _UPPER[mat]
    wanted = np.frombuffer(want, dtype=np.uint8)
    eq = mat == wanted
    if wildcard_n:
        eq |= (wanted == ord("N")) & np.isin(mat, np.frombuffer(b"ACGTN", np.uint8))
    out[cand] = eq.all(axis=1)
    return out


def match_rows(shard: RefShard, q: Query, *, ref_wildcard: bool = False) -> np.ndarray:
    """Every matching row id of one dataset, no caps, byte-exact alleles."""
    c = shard.cols
    code = chrom_code(q.chrom)
    lo, hi = int(shard.chrom_offsets[code]), int(shard.chrom_offsets[code + 1])
    if lo == hi:
        return np.empty(0, np.int64)
    pos = c["pos"][lo:hi]
    a = int(np.searchsorted(pos, q.start_min, side="left"))
    b = int(np.searchsorted(pos, q.start_max, side="right"))
    if a >= b:
        return np.empty(0, np.int64)
    sl = slice(lo + a, lo + b)
    idx = np.arange(lo + a, lo + b)
    rec_end = c["rec_end"][sl]
    ok = (q.end_min <= rec_end) & (rec_end <= q.end_max)
    if q.ref is not None and q.ref != "N":
        ok &= _blob_eq(
            shard.ref_blob, shard.ref_off, idx, c["ref_len"][sl],
            q.ref.encode(), upper=True, wildcard_n=ref_wildcard,
        )
    alt_len = c["alt_len"][sl]
    max_len = 2**31 - 1 if q.max_len < 0 else q.max_len
    ok &= (q.min_len <= alt_len) & (alt_len <= max_len)
    flags = c["flags"][sl]
    f = lambda bit: (flags & bit) != 0
    if q.alt is None:
        sym = f(SYMBOLIC)
        k = c["ref_repeat_k"][sl]
        ref_len = c["ref_len"][sl]
        vt = q.vtype
        # '<' + str(None) is '<None' and matches nothing, as upstream
        pm = _blob_eq(
            shard.alt_blob, shard.alt_off, idx, alt_len,
            ("<" + str(vt)).encode(), upper=False, prefix=True,
        )
        if vt == "DEL":
            alt_ok = np.where(sym, pm | f(CN0), alt_len < ref_len)
        elif vt == "INS":
            alt_ok = np.where(sym, pm, alt_len > ref_len)
        elif vt == "DUP":
            alt_ok = np.where(sym, pm | (f(CN_PREFIX) & ~f(CN0) & ~f(CN1)), k >= 2)
        elif vt == "DUP:TANDEM":
            alt_ok = np.where(sym, pm | f(CN2), k == 2)
        elif vt == "CNV":
            alt_ok = np.where(
                sym, pm | f(CN_PREFIX) | f(DEL_PREFIX) | f(DUP_PREFIX), f(DOT) | (k >= 1)
            )
        else:
            alt_ok = sym & pm
        ok &= alt_ok.astype(bool)
    elif q.alt == "N":
        ok &= f(SINGLE_BASE)
    else:
        ok &= _blob_eq(
            shard.alt_blob, shard.alt_off, idx, alt_len, q.alt.encode(), upper=True
        )
    return idx[ok]


@dataclass
class Answer:
    """What one dataset must answer."""

    dataset_id: str
    vcf_location: str
    exists: bool
    call_count: int
    all_alleles_count: int
    variants: list
    sample_indices: list
    sample_names: list


def answer(shard: RefShard, q: Query, selected: list | None) -> Answer | None:
    """The per-record loop over the matched rows (INFO-sourced counts, the
    corpus every configuration here uses): cumulative call count, the early
    stops of boolean granularity and of include NONE, the variant strings
    of rows with a non-zero allele count, and the carriers among the
    selected samples (positions in the selected list) or the whole cohort.
    ``None`` when the dataset has no such chromosome."""
    code = chrom_code(q.chrom)
    if int(shard.chrom_offsets[code]) == int(shard.chrom_offsets[code + 1]):
        return None
    c = shard.cols
    rows = match_rows(shard, q, ref_wildcard=selected is not None)
    flags = c["flags"][rows]
    if not ((flags & AC_INFO).all() and (flags & AN_INFO).all()):
        raise ValueError("reference: genotype-derived counts are not in this corpus")
    include_details = q.include in ("HIT", "ALL")
    want_samples = q.granularity in ("record", "aggregated") and shard.gt_bits is not None
    exists, call_count, all_alleles = False, 0, 0
    variants: list[str] = []
    carriers: set[int] = set()
    i, n = 0, len(rows)
    while i < n:
        j = i
        rid = c["rec_id"][rows[i]]
        while j < n and c["rec_id"][rows[j]] == rid:
            j += 1
        rec_rows = rows[i:j]
        i = j
        for r in rec_rows:
            r = int(r)
            call_count += int(c["ac"][r])
            if c["ac"][r] != 0:
                variants.append(
                    f"{q.chrom}\t{c['pos'][r]}\t{shard.ref(r)}\t{shard.alt(r)}\t{shard.vt_label}"
                )
        if call_count:
            exists = True
            if not include_details:
                break
            if want_samples:
                for r in rec_rows:
                    # bit ``s`` of the row's little-endian words is sample s
                    bits = np.unpackbits(
                        np.ascontiguousarray(shard.gt_bits[int(r)]).view(np.uint8),
                        bitorder="little",
                    )[: len(shard.sample_names)]
                    if selected is not None:
                        bits = bits[selected]
                    carriers.update(np.flatnonzero(bits).tolist())
        all_alleles += int(c["an"][int(rec_rows[0])])
        if q.granularity == "boolean" and exists:
            break
    names = shard.sample_names
    if selected is not None:
        names = [names[si] for si in selected]
    resolved = []
    if q.granularity in ("record", "aggregated") and names:
        resolved = [s for k, s in enumerate(names) if k in carriers]
    return Answer(
        shard.dataset_id, shard.vcf_location, exists, call_count, all_alleles,
        variants, sorted(carriers), resolved,
    )


def answers(shards: list, q: Query, selected_of) -> list:
    """Per-dataset answers in (dataset, vcf) order. ``selected_of(shard, q)``
    gives the selected sample positions, or None without a filter."""
    out = []
    for shard in sorted(shards, key=lambda s: (s.dataset_id, s.vcf_location)):
        a = answer(shard, q, selected_of(shard, q))
        if a is not None:
            out.append(a)
    return out


def envelope_facts(q: Query, per_dataset: list) -> dict:
    """What the Beacon envelope must carry: exists is an OR; the count is
    the number of distinct variants when resultset details were asked for;
    a record answer lists the first ``limit`` distinct variants after
    ``skip`` in dataset then row order."""
    exists = any(a.exists for a in per_dataset)
    facts = {"exists": exists}
    if q.granularity == "boolean":
        return facts
    distinct: set[str] = set()
    ordered: list[str] = []
    seen_ids: set[str] = set()
    if q.include in ("HIT", "ALL"):
        seen = False
        for a in per_dataset:
            seen = seen or a.exists
            if not seen:
                continue
            distinct.update(a.variants)
            for v in a.variants:
                vid = "\t".join([q.assembly, *v.split("\t")[:4]])
                if vid not in seen_ids:
                    seen_ids.add(vid)
                    ordered.append(base64.b64encode(vid.encode()).decode())
    facts["count"] = len(distinct)
    if q.granularity in ("record", "aggregated"):
        facts["ids"] = ordered[q.skip : q.skip + q.limit]
    return facts


def envelope_mismatch(doc: dict, facts: dict) -> str | None:
    """None when the served envelope carries exactly ``facts``."""
    summary = doc.get("responseSummary") or {}
    if "exists" not in summary or bool(summary["exists"]) != facts["exists"]:
        return f"exists {summary.get('exists')} != {facts['exists']}"
    if "count" in facts and summary.get("numTotalResults") != facts["count"]:
        return f"count {summary.get('numTotalResults')} != {facts['count']}"
    if "ids" in facts:
        got = [
            r["variantInternalId"]
            for rs in doc["response"]["resultSets"]
            for r in rs["results"]
        ]
        if got != facts["ids"]:
            return f"record list differs ({len(got)} vs {len(facts['ids'])} records)"
    return None


ANSWER_FIELDS = (
    "exists", "call_count", "all_alleles_count", "variants", "sample_indices",
    "sample_names",
)


def answers_mismatch(got: list, want: list) -> str | None:
    """None when the engine's per-dataset responses (any objects with the
    fields of :class:`Answer`) equal the reference's, field for field."""
    key = lambda r: (r.dataset_id, r.vcf_location)
    got = sorted(got, key=key)
    want = sorted(want, key=key)
    if [key(r) for r in got] != [key(r) for r in want]:
        return f"targets {[key(r) for r in got][:4]} != {[key(r) for r in want][:4]}"
    for g, w in zip(got, want):
        for f in ANSWER_FIELDS:
            a, b = getattr(g, f), getattr(w, f)
            if list(a) != list(b) if isinstance(b, list) else a != b:
                if isinstance(b, list) and len(b) > 6:
                    a, b = f"{len(a)} items", f"{len(b)} items"
                return f"{g.dataset_id}.{f}: got {a!r}, want {b!r}"
    return None


def stale_copy(shard: RefShard, every: int, offset: int) -> RefShard:
    """The control's corpus: a copy whose allele count differs in one row of
    every ``every`` (a stale index: zero where the data has a count, one
    where it has none). The planes and blobs are shared."""
    cols = dict(shard.cols)
    ac = cols["ac"].copy()
    rows = np.arange(offset % every, len(ac), every)
    ac[rows] = np.where(ac[rows] == 0, 1, 0)
    cols["ac"] = ac
    return RefShard(
        shard.dataset_id, shard.vcf_location, cols, shard.chrom_offsets,
        shard.ref_blob, shard.ref_off, shard.alt_blob, shard.alt_off,
        shard.sample_names, shard.gt_bits, shard.vt_label,
    )


class StaleRows:
    """A carrier plane with every bit flipped in one row of every
    ``every``, read row by row (the plane itself, gigabytes, is shared)."""

    def __init__(self, plane: np.ndarray, every: int, offset: int):
        self.plane, self.every, self.offset = plane, every, offset % every

    def __getitem__(self, row: int) -> np.ndarray:
        words = self.plane[row]
        if row % self.every == self.offset:
            words = ~words
        return words


def stale_planes(shard: RefShard, every: int, offset: int) -> RefShard:
    """The control's corpus for the plane path: the carriers of one row of
    every ``every`` inverted; columns and blobs are shared."""
    plane = None if shard.gt_bits is None else StaleRows(shard.gt_bits, every, offset)
    return RefShard(
        shard.dataset_id, shard.vcf_location, shard.cols, shard.chrom_offsets,
        shard.ref_blob, shard.ref_off, shard.alt_blob, shard.alt_off,
        shard.sample_names, plane, shard.vt_label,
    )
