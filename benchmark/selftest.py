#!/usr/bin/env python3
"""The benchmark's own check, at toy sizes on the CPU: ``python3 benchmark/selftest.py``.

1. the plain reference against a hand-made shard whose answers are
   written out below;
2. the trace reduction on a hand-made event list and on the small trace
   recorded on the chip (``testdata/trace_events.json``);
3. the generator: the same seed gives the same requests, distinct keys
   give distinct requests, a hot set repeats;
4. the whole command at toy sizes for every cell of ``BENCHMARK.json``
   under ``--rehearsal`` (the scatter family forced, as ``chip_smoke.py
   --rehearsal`` does), from a temporary bench root: the copies of the
   data files cut to toy rows, plus a ``kg1.hot`` and an open-loop
   ``kg1.open`` cell that exist only there, each as one traffic file and
   one ``workloads`` entry;
5. the controls: the window's answers held to a corpus stale in its
   allele counts, to one stale in its carrier bits, and to a shifted map
   from filter term to samples, must each come out as not correct;
6. runs whose timed path is broken underneath (a filter resolved to other
   samples, the selection dropped on the way to the engine, the engine's
   answers altered where they are produced) must print ``correct`` false.

It names its platform, runs nothing on a chip, and the lines it reads are
all marked ``rehearsal``: it cannot print a chip result.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(REPO))

#: toy rows are too sparse for ranges to hold many rows, so the toy
#: control's corpus is stale in every second row
TOY = {
    "kg1": {"rows_per_dataset": 60_000, "control": {"stale_rows_every": 2}},
    "mds": {"rows_per_dataset": 12_000, "datasets": 4, "control": {"stale_rows_every": 2}},
}
TOY_TRAFFIC = {
    "clients": 4, "processes": 2, "warm_requests_per_client": 6, "check_sample": 96,
    "check_min": 24,
}
TOY_KEYS = {"snv": 6000, "indel_sv": 1500, "indel": 1200}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}")


# -- 1. the reference on a hand-made shard --------------------------------------


def hand_shard():
    """Chromosome 1, five rows in three records:
    row 0  pos 100 A>G  ac 3        (record 0)
    row 1  pos 100 A>T  ac 0        (record 0, second allele)
    row 2  pos 250 AC>A ac 2        (record 1, a deletion)
    row 3  pos 400 C>G  ac 5        (record 2)
    row 4  pos 900 G><DEL> ac 1     (record 3, symbolic)
    four samples; carriers: row 0 -> {0, 2}, row 3 -> {1, 2, 3}."""
    import numpy as np

    import reference as R

    refs = [b"A", b"A", b"AC", b"C", b"G"]
    alts = [b"G", b"T", b"A", b"G", b"<DEL>"]
    pos = np.array([100, 100, 250, 400, 900], np.int32)
    ref_len = np.array([len(r) for r in refs], np.int32)
    base = R.AC_INFO | R.AN_INFO
    flags = np.array(
        [base | R.SINGLE_BASE, base | R.SINGLE_BASE, base | R.SINGLE_BASE,
         base | R.SINGLE_BASE, base | R.SYMBOLIC | R.DEL_PREFIX], np.int32)
    off = np.zeros(28, np.int32)
    off[2:] = 5
    blob = lambda xs: (np.frombuffer(b"".join(xs), np.uint8),
                       np.cumsum([0] + [len(x) for x in xs]).astype(np.uint32))
    ref_blob, ref_off = blob(refs)
    alt_blob, alt_off = blob(alts)
    gt = np.zeros((5, 1), np.uint32)
    gt[0, 0] = 0b0101
    gt[3, 0] = 0b1110
    return R.RefShard(
        "hand", "synthetic://hand",
        {"pos": pos, "rec_end": pos + ref_len - 1, "ref_len": ref_len,
         "alt_len": np.array([1, 1, 1, 1, 5], np.int32),
         "ref_repeat_k": np.zeros(5, np.int32), "flags": flags,
         "ac": np.array([3, 0, 2, 5, 1], np.int32), "an": np.full(5, 8, np.int32),
         "rec_id": np.array([0, 0, 1, 2, 3], np.int32)},
        off, ref_blob, ref_off, alt_blob, alt_off, ["S0", "S1", "S2", "S3"], gt,
    )


def body(start, end, gran="count", include="HIT", limit=None, **rp):
    q = {"requestedGranularity": gran, "includeResultsetResponses": include,
         "requestParameters": {"assemblyId": "GRCh38", "referenceName": "1",
                               "start": start, "end": end, **rp}}
    if limit is not None:
        q["pagination"] = {"skip": 0, "limit": limit}
    return {"query": q}


def test_reference() -> None:
    import reference as R

    print("reference on the hand-made shard")
    shard = hand_shard()
    ask = lambda b, sel=None: R.answer(shard, R.parse_body(b), sel)
    a = ask(body([99], [100], referenceBases="A", alternateBases="G"))
    check((a.exists, a.call_count, a.all_alleles_count, len(a.variants)) == (True, 3, 8, 1),
          "a point query finds its allele: 3 calls of 8")
    a = ask(body([99], [100], referenceBases="A", alternateBases="T"))
    check((a.exists, a.call_count, a.variants) == (False, 0, []),
          "an allele with no calls does not exist and lists nothing")
    a = ask(body([0], [999], alternateBases="N"))
    check((a.call_count, a.all_alleles_count, len(a.variants)) == (10, 24, 3),
          "a range over every single-base alt: 3+0+2+5 calls, AN once per record")
    a = ask(body([0], [999], "boolean", alternateBases="N"))
    check((a.exists, a.call_count, a.all_alleles_count) == (True, 3, 8),
          "boolean granularity stops after the first record that exists")
    a = ask(body([0], [999], include="NONE", alternateBases="N"))
    check((a.call_count, a.all_alleles_count) == (3, 0),
          "include NONE stops before the record's AN is added")
    a = ask(body([200, 300], [200, 300], variantType="DEL"))
    check((a.call_count, [v.split("\t")[1] for v in a.variants]) == (2, ["250"]),
          "a bracket finds the deletion AC>A by type")
    a = ask(body([800, 950], [800, 950], variantType="DEL"))
    check(a.call_count == 1, "... and the symbolic <DEL>")
    a = ask(body([0], [999], variantType="INS", variantMinLength=30, variantMaxLength=60))
    check(not a.exists, "a length no allele has matches nothing")
    a = ask(body([0], [999], "record", limit=10, alternateBases="N"))
    check(a.sample_names == ["S0", "S1", "S2", "S3"], "record granularity lists every carrier")
    a = ask(body([0], [999], "record", limit=10, alternateBases="N"), [1, 3])
    check((a.sample_indices, a.sample_names) == ([0, 1], ["S1", "S3"]),
          "selected samples: carriers among them, as positions in the selection")
    q = R.parse_body(body([0], [999], "record", limit=2, alternateBases="N"))
    facts = R.envelope_facts(q, [R.answer(shard, q, None)])
    check((facts["count"], len(facts["ids"])) == (3, 2), "the envelope pages the record list")
    stale = R.stale_copy(shard, 2, 0)
    check(R.answer(stale, q, None).call_count != 10, "a stale corpus answers differently")


# -- 2. the trace reduction -----------------------------------------------------


def test_trace() -> None:
    import trace_reduce as T

    print("trace reduction")
    dev = "/device:TPU:0"
    events = [
        ["/host:CPU", "main", "bench.traced_window", 0, 1000],
        [dev, T.MODULE_LINE, "jit__scatter_batch(123)", 100, 200],
        [dev, T.MODULE_LINE, "jit__scatter_batch(123)", 500, 100],
        [dev, T.OPS_LINE, "fusion.1", 100, 150],
        [dev, T.OPS_LINE, "gather.2", 220, 80],
        [dev, T.OPS_LINE, "fusion.1", 500, 100],
        ["/host:CPU", "worker", "encode", 300, 190],
        ["/host:CPU", "worker", "fetch", 610, 300],
    ]
    r = T.reduce_events(events)
    check(abs(r["busy_s"] - 300e-9) < 1e-15 and abs(r["window_s"] - 1000e-9) < 1e-15,
          "busy time is the union of the operations, the window is the harness's annotation")
    check(r["device_ops"][0] == ["fusion.1", 250e-9], "operations are ranked by device time")
    check(r["modules"]["jit__scatter_batch"] == {"launches": 2, "seconds": 300e-9},
          "launches are grouped by program, fingerprints stripped")
    check(r["idle_gaps"][0] == ["fetch", 400e-9] and r["idle_gaps"][1] == ["encode", 200e-9],
          "gaps are named by the host event covering most of each")
    recorded = HERE / "testdata" / "trace_events.json"
    if recorded.exists():
        doc = json.loads(recorded.read_text())
        r = T.reduce_events(doc["events"])
        for key, want in doc["expect"].items():
            got = r[key] if key != "modules" else {k: v["launches"] for k, v in r[key].items()}
            ok = abs(got - want) <= 1e-9 * max(1.0, abs(want)) if isinstance(want, float) else got == want
            check(ok, f"the recorded chip trace reduces to its recorded {key}")
    else:
        print("  (no recorded trace yet)")


# -- 3. the generator -----------------------------------------------------------


def test_generator() -> None:
    import loadgen as L

    print("traffic generator")
    traffic = json.loads((HERE / "traffic" / "unique-mixed.json").read_text())
    keys = {"snv": [("1", 1000 + 7 * i, "A", "G") for i in range(5000)],
            "indel_sv": [("2", 5000 + 11 * i, "ACG", "A") for i in range(2000)],
            "indel": [("3", 9000 + 13 * i, "A", "ACGT") for i in range(2000)]}
    facts = {"assembly": "GRCh38", "chrom_lengths": {"1": 10**6, "2": 10**6, "3": 10**6},
             "terms": ["T:1"], "datasets": ["d0"]}

    def stream(seed, client, n=200, **over):
        t = {**traffic, **over}
        draw = L.KeyDraw(t, keys, seed, client, 4, warm=False)
        return [json.dumps(L.request_for(t, facts, keys, seed, *draw.next()), sort_keys=True)
                for _ in range(n)]

    check(stream(2**31 + 5, 0) == stream(2**31 + 5, 0), "the same seed gives the same requests")
    check(stream(2**31 + 5, 0) != stream(2**31 + 6, 0), "another seed gives others")
    both = stream(9, 0) + stream(9, 1)
    check(len(set(both)) == len(both), "two clients' requests are all distinct")
    hot = stream(9, 0, reuse={"hot_share": 0.8, "hot_keys": 20, "zipf": 0.99})
    check(len(set(hot)) < len(hot) // 2, "a hot set repeats requests")
    classes = {json.loads(s)[0] for s in stream(9, 0, 600)}
    check(len(classes) == 12, "every shape and granularity of the mix is drawn")


# -- 4-6. the whole command at toy sizes ----------------------------------------


def toy_root(tmp: Path) -> Path:
    """A bench root whose data files are the repository's, cut to toy rows,
    plus one cell that exists only here: ``kg1.hot``."""
    root = tmp / "root"
    (root / "benchmark").mkdir(parents=True)
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(HERE / sub, root / "benchmark" / sub)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, over in TOY.items():
        path = root / "benchmark" / "configs" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **over}))
    for path in (root / "benchmark" / "traffic").glob("*.json"):
        t = {**json.loads(path.read_text()), **TOY_TRAFFIC}
        t["keys_per_class"] = {k: TOY_KEYS[k] for k in t["keys_per_class"]}
        path.write_text(json.dumps(t))
    # what a later PR would add for kg1.hot: one traffic file, one entry
    hot = json.loads((root / "benchmark" / "traffic" / "unique-mixed.json").read_text())
    hot["reuse"] = {"hot_share": 0.8, "hot_keys": 200, "zipf": 0.99}
    (root / "benchmark" / "traffic" / "hot-mixed.json").write_text(json.dumps(hot))
    bench["workloads"].append(
        {"name": "kg1.hot", "config": "kg1", "traffic": "hot-mixed", "chips": 1,
         "why": "80% of requests from a hot set of keys: the response cache does the work"}
    )
    # ... and for kg1.open: Poisson arrivals at a fixed rate, with bursts
    arrivals = json.loads((root / "benchmark" / "traffic" / "unique-mixed.json").read_text())
    arrivals["arrival"] = {"mode": "open", "rate_per_s": 30, "burst_factor": 3,
                           "burst_every_s": 2, "burst_len_s": 0.5}
    (root / "benchmark" / "traffic" / "open-mixed.json").write_text(json.dumps(arrivals))
    bench["workloads"].append(
        {"name": "kg1.open", "config": "kg1", "traffic": "open-mixed", "chips": 1,
         "why": "open loop: Poisson arrivals at a fixed rate with bursts"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root: Path, workload: str, seed: int, *, trace: int = 0, control: int = 0):
    """One rehearsal run in this process; (exit code, every JSON line)."""
    import run as bench_run

    out = io.StringIO()
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "3",
            "--trace", str(trace), "--control", str(control), "--rehearsal",
            "--bench-root", str(root)]
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(argv)
    return rc, [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]


def test_command(tmp: Path) -> None:
    import jax

    platform = jax.devices()[0].platform
    print(f"the whole command at toy sizes, platform {platform!r} (a rehearsal, never a chip result)")
    root = toy_root(tmp)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in bench["end_to_end"]}
    per_layer = {m["name"] for m in bench["per_layer"]}
    for i, cell in enumerate(bench["workloads"]):
        name = cell["name"]
        rc, lines = run_cell(root, name, 2**31 + 17 + i, control=1)
        last = lines[-1]
        check(rc == 0 and last.get("rehearsal") is True and last["device"]["platform"] == platform,
              f"{name}: ran, marked as a rehearsal on {platform!r}")
        check(set(last) == {"correct", "attempted", "failed", "metrics", "device", "rehearsal"},
              f"{name}: the line has the contract's keys")
        check(last["correct"] is True and last["failed"] == 0 and last["attempted"] > 50,
              f"{name}: correct, {last['attempted']} requests, none failed")
        check(set(last["metrics"]) == e2e, f"{name}: every end-to-end metric")
        control = next(l["control"] for l in lines if "control" in l)
        check(all(n > 0 for n in control["answers_wrong"].values())
              and not control["comes_out_correct"],
              f"{name}: every control comes out as not correct "
              f"({control['answers_wrong']} of {control['answers_compared']} answers)")
        if name == "kg1.open":
            # 30 a second, three times that for a quarter of the time: 135 in 3 s
            check(90 <= last["attempted"] <= 190,
                  "kg1.open: open-loop arrivals with bursts, at the file's own rate")
        if name == "kg1.samples":
            check(set(control["answers_wrong"]) == {
                "stale_allele_counts", "stale_carrier_bits", "shifted_term_map"},
                "kg1.samples: the controls reach the counts, the plane reads and the filter")
        facts = next(l for l in lines if "launches_by_family_in_window" in l)
        print(f"    launches by family: {facts['launches_by_family_in_window']}")
    rc, lines = run_cell(root, "kg1.unique", 2**31 + 99, trace=1)
    last = lines[-1]
    check(rc == 0 and set(last["metrics"]) <= per_layer and "breakdown" in last
          and {"busy_s", "window_s"} <= set(last["device"]),
          f"a traced run reports per-layer metrics: {sorted(last['metrics'])}")
    unique_lpq = last["metrics"]["launches_per_query"]["value"]
    rc, lines = run_cell(root, "kg1.hot", 2**31 + 3, trace=1)
    hot_lpq = lines[-1]["metrics"]["launches_per_query"]["value"]
    check(rc == 0 and hot_lpq < 0.75 * unique_lpq,
          "kg1.hot, a cell of one traffic file and one entry, is served from memory: "
          f"{hot_lpq:.2f} device launches a request against {unique_lpq:.2f} in kg1.unique")

    print("runs with the timed path broken underneath")
    import dataclasses

    import sbeacon_tpu.api.app as app_mod
    import sbeacon_tpu.engine as engine_mod

    resolve = app_mod.resolve_datasets

    def other_samples(*a, **kw):
        datasets, samples = resolve(*a, **kw)
        return datasets, {ds: [f"S{int(n[1:]) + 1}" for n in names] for ds, names in samples.items()}

    def no_selection(*a, **kw):
        return resolve(*a, **kw)[0], {}

    for broken, what in ((other_samples, "the filter resolved to the neighbouring samples"),
                         (no_selection, "the API path dropped the selection")):
        app_mod.resolve_datasets = broken
        try:
            rc, lines = run_cell(root, "kg1.samples", 2**31 + 43)
        finally:
            app_mod.resolve_datasets = resolve
        verdict = next(l["check"] for l in lines if "check" in l)
        check(rc == 0 and lines[-1]["correct"] is False
              and verdict["answers_wrong"] == verdict["answers_compared"],
              f"{what}: correct is false (all {verdict['answers_wrong']} answers differ)")

    sound = engine_mod.materialize_response

    def off_by_one(*a, **kw):
        r = sound(*a, **kw)
        return dataclasses.replace(r, call_count=r.call_count + 1) if r.exists else r

    engine_mod.materialize_response = off_by_one
    try:
        rc, lines = run_cell(root, "kg1.unique", 2**31 + 41)
    finally:
        engine_mod.materialize_response = sound
    verdict = next(l["check"] for l in lines if "check" in l)
    check(rc == 0 and lines[-1]["correct"] is False and verdict["answers_wrong"] > 0,
          f"call counts off by one where they are produced: correct is false "
          f"({verdict['answers_wrong']} answers differ)")


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    tmp = Path(tempfile.mkdtemp(prefix="bench_selftest_"))
    # CPU programs stay out of the checkout's .jax_cache
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp / "jax_cache"))
    try:
        test_reference()
        test_trace()
        test_generator()
        if "--quick" not in sys.argv:
            test_command(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("selftest passed")
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
