"""A configuration file -> the corpus of one run, all from ``--seed``.

The shards are built by ``sbeacon_tpu.testing.synthetic_shard`` (program
code that builds the program's own shard format); its parameters live in
``benchmark/configs/<name>.json``. From the shards come the key table the
load generator draws requests from, the facts it needs, the metadata the
server is given over ``/submit``, and the reference's view of the columns.
"""

from __future__ import annotations

import csv

import numpy as np

from reference import SINGLE_BASE, SYMBOLIC

SEX_TERMS = ("NCIT:C16576", "NCIT:C20197")  # female, male


def dataset_ids(config: dict) -> list[str]:
    return [f"{config['dataset_prefix']}{i}" for i in range(config["datasets"])]


def disease_terms(config: dict) -> list[str]:
    n = int((config.get("metadata") or {}).get("disease_terms", 0))
    return [f"MONDO:{5000 + t:07d}" for t in range(n)]


#: rows of a plane that one seeded generator fills; the layout of the
#: random stream, so it never depends on how many threads fill it
PLANE_BLOCK_ROWS = 1 << 18


def make_gt_plane(n_rows: int, n_samples: int, density: float, seed: int) -> np.ndarray:
    """uint32[n_rows, ceil(n_samples/32)] carrier bits, ``density`` of them
    set (the AND of k random words thins to 2^-k), made block by block on a
    few threads: numpy's generators release the interpreter lock.

    ``synthetic_shard(with_gt_planes=True)`` makes the same ``gt_bits`` plus
    three count planes the serving path never uploads for INFO-sourced
    counts; at 2e7 rows x 2504 samples that took 188 s and 28 GB of host
    memory on one thread (PERF.md), for bytes no request reads."""
    from concurrent.futures import ThreadPoolExecutor

    words = (n_samples + 31) // 32
    k_and = max(1, int(round(-np.log2(max(density, 2**-16)))))
    out = np.empty((n_rows, words), np.uint32)
    starts = range(0, n_rows, PLANE_BLOCK_ROWS)
    seeds = np.random.SeedSequence(seed).spawn(len(starts))
    tail = n_samples % 32

    def fill(job):
        a, ss = job
        b = min(a + PLANE_BLOCK_ROWS, n_rows)
        rng = np.random.default_rng(ss)
        n64 = -(-(b - a) * words // 2)
        g = rng.bit_generator.random_raw(n64)
        for _ in range(k_and - 1):
            g &= rng.bit_generator.random_raw(n64)
        out[a:b] = g.view(np.uint32)[: (b - a) * words].reshape(b - a, words)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, zip(starts, seeds)))
    if tail:
        out[:, -1] &= np.uint32((1 << tail) - 1)
    return out


def make_shards(config: dict, seed: int) -> dict:
    """{dataset id: shard}; dataset ``i`` is seeded ``seed + i``. Datasets
    are made on a few threads (numpy releases the interpreter lock): the
    result does not depend on how many."""
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    from sbeacon_tpu.testing import synthetic_shard

    def make(job):
        i, ds = job
        shard = synthetic_shard(
            config["rows_per_dataset"],
            n_samples=config["n_samples"],
            seed=seed + i,
            dataset_id=ds,
            **config["synthetic_shard"],
        )
        plane = config.get("gt_plane")
        if plane:
            shard = dataclasses.replace(
                shard,
                gt_bits=make_gt_plane(
                    shard.n_rows, config["n_samples"], plane["density"], seed + 7919 * (i + 1)
                ),
            )
        return ds, shard

    ids = dataset_ids(config)
    with ThreadPoolExecutor(max_workers=min(8, len(ids))) as pool:
        return dict(pool.map(make, enumerate(ids)))


def selected_positions(config: dict, term: str) -> list[int]:
    """Sample positions the term selects: individual ``i`` carries term
    ``i mod n_terms``."""
    terms = disease_terms(config)
    t = terms.index(term)
    return [i for i in range(config["n_samples"]) if i % len(terms) == t]


def metadata_submission(config: dict, ds: str, samples: list[str]) -> dict:
    """One /submit body: the dataset doc plus, for every sample, the chain
    individual -> biosample -> run -> analysis (``vcfSampleId``), as
    ``chip_smoke.metadata_submission`` builds them."""
    body = {
        "datasetId": ds,
        "assemblyId": config["assembly"],
        "vcfLocations": [],
        "dataset": {"name": ds, "description": config["name"]},
        "index": True,
    }
    terms = disease_terms(config)
    if samples and terms:
        idx = range(len(samples))
        body["individuals"] = [
            {
                "id": f"{ds}-I{i}",
                "sex": {"id": SEX_TERMS[i % 2], "label": "-"},
                "diseases": [{"diseaseCode": {"id": terms[i % len(terms)]}}],
            }
            for i in idx
        ]
        body["biosamples"] = [{"id": f"{ds}-B{i}", "individualId": f"{ds}-I{i}"} for i in idx]
        body["runs"] = [
            {"id": f"{ds}-R{i}", "biosampleId": f"{ds}-B{i}", "individualId": f"{ds}-I{i}"}
            for i in idx
        ]
        body["analyses"] = [
            {
                "id": f"{ds}-A{i}", "runId": f"{ds}-R{i}", "biosampleId": f"{ds}-B{i}",
                "individualId": f"{ds}-I{i}", "vcfSampleId": samples[i],
            }
            for i in idx
        ]
    return body


KEY_CLASSES = {
    # single-base substitutions: what a point query names
    "snv": lambda c, sym: (~sym) & (c["ref_len"] == 1) & (c["alt_len"] == 1)
    & ((c["flags"] & SINGLE_BASE) != 0),
    # length-changing plain alleles
    "indel": lambda c, sym: (~sym) & (c["alt_len"] != c["ref_len"]) & (c["alt_len"] > 1),
    # indels and symbolic alleles: what a bracket query is for
    "indel_sv": lambda c, sym: (sym | (c["alt_len"] != c["ref_len"])) & (c["alt_len"] > 1),
}


def write_keys(shards: dict, ref_shards: list, per_class: dict, seed: int, path: str) -> dict:
    """Draw ``per_class[cls]`` rows of each key class over all datasets,
    without replacement, and write them as ``cls,chrom,pos,ref,alt``.
    Returns the facts of the corpus the generator needs."""
    rng = np.random.default_rng(seed ^ 0x5BEAC0)
    lengths: dict[str, int] = {}
    refs = dict(zip(shards, ref_shards))
    with open(path, "w", newline="") as fh:
        out = csv.writer(fh)
        for cls, want in per_class.items():
            share = -(-int(want) // len(shards))
            rows_of = []
            for ds, shard in shards.items():
                c = shard.cols
                sym = (c["flags"] & SYMBOLIC) != 0
                cand = np.flatnonzero(KEY_CLASSES[cls](c, sym))
                take = rng.choice(cand, size=min(share, len(cand)), replace=False)
                codes = np.searchsorted(shard.chrom_offsets, take, side="right") - 1
                rows_of.append((ds, take, codes))
            # interleave the datasets, so that any prefix spans them all
            for k in range(share):
                for ds, take, codes in rows_of:
                    if k < len(take):
                        r = int(take[k])
                        out.writerow([cls, str(int(codes[k])), int(shards[ds].cols["pos"][r]),
                                      refs[ds].ref(r), refs[ds].alt(r)])
    for shard in shards.values():
        off = shard.chrom_offsets
        for code in range(1, len(off) - 1):
            if off[code + 1] > off[code]:
                top = int(shard.cols["pos"][off[code + 1] - 1])
                lengths[str(code)] = max(lengths.get(str(code), 0), top)
    return {"chrom_lengths": lengths}
