"""1000-Genomes-scale harness: generator output is real-pipeline food.

A scale run happens out-of-band; these tests pin the properties the
scale proof depends on: generated AC/AN INFO is exactly consistent with the painted GT carriers after a
trip through the REAL ingest pipeline, and the per-chromosome driver
is resumable.
"""

import json

import numpy as np

from sbeacon_tpu.harness.genome1k import (
    build_corpus,
    chrom_record_counts,
    load_merged,
    write_cohort_vcf,
)


def test_generated_cohort_through_real_pipeline(tmp_path):
    m = build_corpus(
        tmp_path,
        total_records=2500,
        n_samples=37,  # non-multiple of 32: exercises the tail word
        chroms=["21", "22"],
        seed=5,
    )
    assert m["totals"]["records"] == 2500
    shard = load_merged(tmp_path, ["21", "22"])
    assert shard.n_rows >= 2500
    assert shard.meta["sample_count"] == 37
    c = shard.cols
    # INFO AC must equal painted carriers (>=1 copies + >=2 copies)
    g1 = np.bitwise_count(shard.gt_bits).sum(axis=1)
    g2 = np.bitwise_count(shard.gt_bits2).sum(axis=1)
    np.testing.assert_array_equal(c["ac"], g1 + g2)
    assert (c["an"] == 74).all()
    t1 = np.bitwise_count(shard.tok_bits1).sum(axis=1)
    assert (t1 == 37).all()  # every sample genotyped
    # per-chrom position sort survives the merge
    off = shard.chrom_offsets
    for code in range(26):
        seg = c["pos"][off[code] : off[code + 1]]
        assert (np.diff(seg) >= 0).all()


def test_build_corpus_resumes(tmp_path):
    build_corpus(
        tmp_path, total_records=600, n_samples=5, chroms=["22"], seed=2
    )
    manifest = json.loads((tmp_path / "manifest.json").read_text())
    first = manifest["chroms"]["22"]
    # second invocation: chromosome already done -> untouched timings
    build_corpus(
        tmp_path, total_records=600, n_samples=5, chroms=["22"], seed=2
    )
    manifest2 = json.loads((tmp_path / "manifest.json").read_text())
    assert manifest2["chroms"]["22"] == first


def test_chrom_record_counts_proportional():
    counts = chrom_record_counts(1_000_000, [str(i) for i in range(1, 23)])
    assert sum(counts.values()) == 1_000_000
    assert counts["1"] > counts["22"] * 3  # chr1 ~5x chr22 length


def test_clustered_positions(tmp_path):
    out = write_cohort_vcf(
        tmp_path / "c.vcf.gz",
        chrom="20",
        n_records=4000,
        n_samples=4,
        seed=8,
        position_model="clustered",
    )
    assert out["records"] == 4000


def test_multiallelic_alts_distinct_from_ref(tmp_path):
    """Both ALTs differ from REF and from each other (an earlier rotation
    bug emitted ALT==REF for a few percent of multi-allelic lines)."""
    from sbeacon_tpu.genomics.bgzf import BgzfReader

    p = tmp_path / "m.vcf.gz"
    write_cohort_vcf(
        p, chrom="22", n_records=3000, n_samples=2, seed=1,
        p_multiallelic=1.0, p_indel=0.0,
    )
    checked = 0
    for line in BgzfReader(p).read_all().decode().splitlines():
        if line.startswith("#"):
            continue
        f = line.split("\t")
        ref, alts = f[3], f[4].split(",")
        assert len(alts) == 2
        assert alts[0] != ref and alts[1] != ref and alts[0] != alts[1], line
        checked += 1
    assert checked == 3000
