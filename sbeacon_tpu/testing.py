"""Synthetic data generation for tests, benchmarks and simulations.

Plays the role of the reference's simulation generator (reference:
simulations/simulate.py — synthetic populations from a template VCF), but
generates structured-random VCF records directly, covering every branch of
the variant-matching semantics: SNPs, indels, multi-alt records, symbolic
alleles (<DEL>, <DUP>, <CN0>...), records with and without INFO AC/AN, and
genotype columns.
"""

from __future__ import annotations

import random
from pathlib import Path

from .genomics.vcf import VcfRecord, write_vcf

BASES = "ACGT"

SYMBOLIC_ALTS = [
    "<DEL>",
    "<INS>",
    "<DUP>",
    "<DUP:TANDEM>",
    "<CN0>",
    "<CN1>",
    "<CN2>",
    "<CN3>",
    "<INV>",
]


def _random_seq(rng: random.Random, lo: int, hi: int) -> str:
    return "".join(rng.choice(BASES) for _ in range(rng.randint(lo, hi)))


def random_records(
    rng: random.Random,
    chrom: str = "1",
    n: int = 500,
    start: int = 1000,
    spacing: int = 30,
    n_samples: int = 8,
    p_multiallelic: float = 0.15,
    p_symbolic: float = 0.08,
    p_no_acan: float = 0.2,
    p_indel: float = 0.2,
) -> list[VcfRecord]:
    """Generate sorted synthetic records exercising all matcher branches."""
    records = []
    pos = start
    for _ in range(n):
        pos += rng.randint(1, spacing)
        ref = _random_seq(rng, 1, 1) if rng.random() > p_indel else _random_seq(rng, 1, 6)
        n_alts = 2 if rng.random() < p_multiallelic else 1
        alts = []
        for _ in range(n_alts):
            r = rng.random()
            if r < p_symbolic:
                alts.append(rng.choice(SYMBOLIC_ALTS))
            elif r < p_symbolic + 0.1 and len(ref) <= 3:
                # duplication-shaped alt: ref repeated k times
                alts.append(ref * rng.randint(2, 3))
            else:
                alt = _random_seq(rng, 1, 6)
                while alt == ref:
                    alt = _random_seq(rng, 1, 6)
                alts.append(alt)
        # genotypes: diploid calls over alleles 0..n_alts
        genotypes = []
        for _ in range(n_samples):
            a = rng.randint(0, n_alts)
            b = rng.randint(0, n_alts)
            sep = rng.choice("|/")
            genotypes.append(f"{a}{sep}{b}")
        vt = rng.choice(["SNP", "INDEL", "SV", "N/A"])
        rec = VcfRecord(
            chrom=chrom,
            pos=pos,
            ref=ref,
            alts=alts,
            ac=None,
            an=None,
            vt=vt,
            genotypes=genotypes,
        )
        if rng.random() >= p_no_acan:
            # derive INFO AC/AN through the one shared implementation
            rec.ac = rec.effective_ac()
            rec.an = rec.effective_an()
        records.append(rec)
    return records


def make_test_vcf(
    path: str | Path,
    seed: int = 0,
    chroms: tuple[str, ...] = ("1",),
    n_per_chrom: int = 500,
    n_samples: int = 8,
    **kw,
) -> list[VcfRecord]:
    """Write a synthetic bgzipped VCF; returns its records."""
    rng = random.Random(seed)
    records: list[VcfRecord] = []
    for chrom in chroms:
        records.extend(
            random_records(rng, chrom=chrom, n=n_per_chrom, n_samples=n_samples, **kw)
        )
    write_vcf(path, records, sample_names=[f"S{i:04d}" for i in range(n_samples)])
    return records


# ---------------------------------------------------------------------------
# Range-supporting HTTP object server (tests + demos of the object-store
# data plane; stdlib http.server does not honour Range)
# ---------------------------------------------------------------------------


def range_server(directory: str | Path, *, require_token: str = ""):
    """Context manager serving ``directory`` over HTTP with Range support.

    Yields the base URL. Emulates the object-store role (ranged GETs per
    reference downloader.h); ``require_token`` additionally demands an
    ``Authorization`` header equal to it (for exercising the s3://
    BEACON_S3_TOKEN path).
    """
    import contextlib
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    root = Path(directory)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if require_token and (
                self.headers.get("Authorization", "") != require_token
            ):
                self.send_response(403)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            target = (root / self.path.lstrip("/")).resolve()
            if not str(target).startswith(str(root.resolve())) or (
                not target.is_file()
            ):
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            data = target.read_bytes()
            rng = self.headers.get("Range")
            if rng and rng.startswith("bytes="):
                spec = rng[len("bytes="):]
                start_s, _, end_s = spec.partition("-")
                start = int(start_s) if start_s else 0
                end = int(end_s) + 1 if end_s else len(data)
                end = min(end, len(data))
                if start >= len(data):
                    self.send_response(416)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                body = data[start:end]
                self.send_response(206)
                self.send_header(
                    "Content-Range", f"bytes {start}-{end - 1}/{len(data)}"
                )
            else:
                body = data
                self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.send_header("Accept-Ranges", "bytes")
            self.end_headers()
            self.wfile.write(body)

    @contextlib.contextmanager
    def _cm():
        server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            yield f"http://127.0.0.1:{server.server_address[1]}"
        finally:
            server.shutdown()
            server.server_close()

    return _cm()


# ---------------------------------------------------------------------------
# Vectorised large-scale synthetic index (1000-Genomes-shaped corpora)
# ---------------------------------------------------------------------------


def synthetic_shard(
    n_rows: int,
    *,
    n_samples: int = 0,
    seed: int = 0,
    dataset_id: str = "synth",
    chroms: list[str] | None = None,
    position_model: str = "uniform",
    p_multiallelic: float = 0.08,
    p_indel: float = 0.12,
    p_symbolic: float = 0.01,
    with_gt_planes: bool = False,
    plane_density: float = 0.01,
):
    """Directly-constructed ``VariantIndexShard`` at arbitrary scale.

    Pure vectorised numpy — no VCF text, no per-record Python — so a
    2e7-row 1000-Genomes-shaped index builds in seconds. This is the
    query-side scale corpus for benchmarks (the ingest pipeline is
    proven separately through real VCF text); the column *contents* are
    semantically valid (sorted positions per chromosome, contiguous
    multi-alt records sharing pos/AN, correct flags/hashes/prefixes for
    every allele string, AC drawn from a 1/x allele-frequency spectrum,
    blobs materialisable), so host-matcher parity and response
    materialisation work exactly as on ingested data.

    ``position_model``: 'uniform' spreads rows evenly across each
    chromosome's real GRCh38 length; 'clustered' mixes 70% uniform with
    30% hotspot-clustered positions (real genomes are not uniform;
    VERDICT r2 #8).

    All rows carry AC_INFO/AN_INFO (INFO-sourced counts, the common
    case for cohort VCFs), so genotype planes — generated when
    ``with_gt_planes`` with ~``plane_density`` bits set — affect only
    sample extraction, exactly as for bcftools-INFO data.
    """
    import numpy as np

    from .index.columnar import (
        FLAG,
        N_CHROM_CODES,
        VariantIndexShard,
        _alt_flags,
        _ref_repeat_k,
        fnv1a32,
        pack_prefix16,
    )
    from .utils.chrom import CHROMOSOME_LENGTHS, chromosome_code

    rng = np.random.default_rng(seed)
    chroms = chroms or [str(i) for i in range(1, 23)]
    lengths = np.array([CHROMOSOME_LENGTHS[c] for c in chroms], np.float64)
    weights = lengths / lengths.sum()

    # records -> rows: multi-allelic records carry 2-3 alts. Generate
    # one candidate record per requested row (always enough, each
    # record yields >= 1 row), cut at the record whose rows reach
    # n_rows.
    n_rec_est = n_rows + 8
    n_alts = np.where(
        rng.random(n_rec_est) < p_multiallelic,
        rng.integers(2, 4, n_rec_est),
        1,
    ).astype(np.int64)
    total = np.cumsum(n_alts)
    n_rec = min(int(np.searchsorted(total, n_rows, side="left")) + 1, n_rec_est)
    n_alts = n_alts[:n_rec]
    n = int(n_alts.sum())

    # per-record chromosome + position (sorted within chrom)
    rec_chrom = rng.choice(len(chroms), size=n_rec, p=weights)
    u = rng.random(n_rec)
    if position_model == "clustered":
        hot = rng.random(n_rec) < 0.3
        centers = rng.random(64)
        c_idx = rng.integers(0, 64, n_rec)
        spread = rng.normal(0.0, 0.004, n_rec)
        u = np.where(hot, np.clip(centers[c_idx] + spread, 0.0, 1.0), u)
    rec_pos = (u * (lengths[rec_chrom] - 1)).astype(np.int64) + 1

    # sort records by (chromosome CODE, pos) — shard layout is ordered
    # by code, which need not match the chroms list's order
    codes = np.array([chromosome_code(c) for c in chroms], np.int32)
    order = np.lexsort((rec_pos, codes[rec_chrom]))
    rec_chrom = rec_chrom[order]
    rec_pos = rec_pos[order]
    n_alts = n_alts[order]
    row_rec = np.repeat(np.arange(n_rec, dtype=np.int64), n_alts)

    # allele vocabulary: single bases, short indel strings, symbolic
    vocab = ["A", "C", "G", "T"]
    indel_rng = random.Random(seed + 1)
    for _ in range(60):
        vocab.append(_random_seq(indel_rng, 2, 24))
    vocab += ["<DEL>", "<DUP>", "<CN0>", "<CN2>", "<INS>", "."]
    V = len(vocab)
    v_bytes = [v.encode() for v in vocab]
    v_len = np.array([len(v) for v in vocab], np.int64)
    v_hash = np.array([fnv1a32(v.upper().encode()) for v in vocab], np.int32)
    v_flags = np.array([_alt_flags(v) for v in vocab], np.int32)
    v_prefix = np.stack([pack_prefix16(b) for b in v_bytes]).astype(np.uint32)

    kind = rng.random(n)
    is_sym = kind < p_symbolic
    is_indel = (~is_sym) & (kind < p_symbolic + p_indel)
    alt_id = np.where(
        is_sym,
        rng.integers(64, 64 + 6, n),
        np.where(is_indel, rng.integers(4, 64, n), rng.integers(0, 4, n)),
    )
    ref_id = np.repeat(
        np.where(
            rng.random(n_rec) < p_indel / 2,
            rng.integers(4, 64, n_rec),
            rng.integers(0, 4, n_rec),
        ),
        n_alts,
    )

    pos_row = rec_pos[row_rec].astype(np.int32)
    ref_len = v_len[ref_id].astype(np.int32)
    alt_len = v_len[alt_id].astype(np.int32)

    # AC from a heavy-tailed spectrum; AN constant per record
    an_val = 2 * n_samples if n_samples else 5008
    ac = np.minimum(
        (1.0 / np.maximum(rng.random(n), 1e-6)).astype(np.int64), an_val
    ).astype(np.int32)
    ac[rng.random(n) < 0.02] = 0  # monomorphic-in-subset rows

    # repeat-k: vocab pair lookup (cached per unique pair id)
    pair = ref_id * V + alt_id
    uniq_pair, inv = np.unique(pair, return_inverse=True)
    k_u = np.array(
        [
            _ref_repeat_k(vocab[int(p) // V], vocab[int(p) % V])
            for p in uniq_pair
        ],
        np.int32,
    )
    flags = (
        v_flags[alt_id]
        | np.int32(FLAG.AC_INFO)
        | np.int32(FLAG.AN_INFO)
    )

    cols = {
        "pos": pos_row,
        "rec_end": (pos_row.astype(np.int64) + ref_len - 1).astype(np.int32),
        "ref_len": ref_len,
        "alt_len": alt_len,
        "ref_hash": v_hash[ref_id],
        "alt_hash": v_hash[alt_id],
        "ref_repeat_k": k_u[inv],
        "flags": flags,
        "ac": ac,
        "an": np.full(n, an_val, np.int32),
        "rec_id": row_rec.astype(np.int32),
        "alt_prefix": v_prefix[alt_id],
    }

    row_code = codes[rec_chrom[row_rec]]
    chrom_offsets = np.zeros(N_CHROM_CODES + 1, np.int32)
    for c in range(N_CHROM_CODES + 1):
        chrom_offsets[c] = np.searchsorted(row_code, c, side="left")

    # blobs: fixed-width vocab matrix -> masked flatten (vectorised)
    maxw = int(v_len.max())
    v_mat = np.zeros((V, maxw), np.uint8)
    for i, b in enumerate(v_bytes):
        v_mat[i, : len(b)] = np.frombuffer(b, np.uint8)
    lane = np.arange(maxw)

    def blob_of(ids, lens):
        mat = v_mat[ids]
        mask = lane[None, :] < lens[:, None]
        off = np.zeros(n + 1, np.uint32)
        np.cumsum(lens, out=off[1:] if n else None)
        return mat[mask], off

    ref_blob, ref_off = blob_of(ref_id, v_len[ref_id])
    alt_blob, alt_off = blob_of(alt_id, v_len[alt_id])

    planes = {}
    if n_samples and with_gt_planes:
        words = (n_samples + 31) // 32
        # ~plane_density bits set: AND of k random words thins 2^-k
        k_and = max(1, int(round(-np.log2(max(plane_density, 2**-16)))))
        g = rng.integers(0, 2**32, (n, words), dtype=np.uint32)
        for _ in range(k_and - 1):
            g &= rng.integers(0, 2**32, (n, words), dtype=np.uint32)
        tail = n_samples % 32
        if tail:
            g[:, -1] &= np.uint32((1 << tail) - 1)
        planes = {
            "gt_bits": g,
            "gt_bits2": (
                g & rng.integers(0, 2**32, (n, words), dtype=np.uint32)
            ),
            "tok_bits1": np.full(
                (n, words), 0xFFFFFFFF, np.uint32
            ),
            "tok_bits2": np.full((n, words), 0xFFFFFFFF, np.uint32),
            "gt_overflow": np.zeros((0, 3), np.int64),
            "tok_overflow": np.zeros((0, 3), np.int64),
        }
        if tail:
            planes["tok_bits1"][:, -1] = np.uint32((1 << tail) - 1)
            planes["tok_bits2"][:, -1] = np.uint32((1 << tail) - 1)

    meta = {
        "dataset_id": dataset_id,
        "vcf_location": f"synthetic://{dataset_id}",
        "sample_names": [f"S{i}" for i in range(n_samples)],
        "vt_vocab": ["N/A"],
        "n_rows": n,
        "n_records": n_rec,
        "dropped_records": 0,
        "variant_count": n,
        "call_count": int(an_val) * n_rec,
        "sample_count": n_samples,
        "chrom_native": {c: c for c in chroms},
        "format_version": 1,
        "synthetic": True,
        "position_model": position_model,
    }
    return VariantIndexShard(
        meta=meta,
        cols=cols,
        chrom_offsets=chrom_offsets,
        ref_blob=ref_blob.astype(np.uint8),
        ref_off=ref_off,
        alt_blob=alt_blob.astype(np.uint8),
        alt_off=alt_off,
        vt_codes=np.zeros(n, np.int16),
        **planes,
    )
