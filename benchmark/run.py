#!/usr/bin/env python3
"""One run of one benchmark cell.

``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

ONE process that holds the cell's chips. It reads the cell, its
configuration and its traffic from data files; generates the corpus from
``--seed``; starts the server the way ``python -m sbeacon_tpu.api.server``
does, on ``BeaconConfig`` defaults; lets the load generator's children
(standard library only, no JAX) send an unmeasured warm-up of the cell's
own traffic and then measure for ``--seconds``; holds a seeded sample of
the answers served inside the window, with what the engine answered those
requests and the samples their filters were resolved to, to the plain
reference; prints the contract line last. With no TPU it exits
non-zero and prints no result. ``--rehearsal`` (the self-test's switch)
runs on whatever platform JAX finds, names that platform in the line and
marks the line, so it can never be read as a chip result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import http.client  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(REPO))

#: seconds of load before the window opens: the closed loop is in its
#: steady state at both ends of the window
RAMP_S = 1.0
#: the traced part of a ``--trace 1`` window
TRACE_S = 3.0


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Stages:
    """Wall seconds per set-up stage."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0
            say(f"{name} {self.seconds[name]:.1f}s")


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


_MODULES: dict = {}


def load_module(path: Path):
    """A reader or roofline module, by path, loaded once."""
    if path in _MODULES:
        return _MODULES[path]
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def find_cell(root: Path, workload: str) -> dict:
    """The cell with its configuration, traffic and layer files, all found
    by the names in ``BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    base = root / bench["paths"][0]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(root / config_entry["file"]),
        "traffic": load_json(base / "traffic" / f"{cell['traffic']}.json"),
        "layers_dir": base / "layers",
    }


def quantile(xs: list, q: float) -> float:
    """The q-th percentile of all samples (nearest rank on the sorted list,
    interpolated): the tail of all requests, nothing trimmed."""
    xs = sorted(xs)
    if not xs:
        return float("nan")
    at = (len(xs) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (at - lo)


def longest_gap(records: list, seconds: float) -> float:
    """The longest interval of the window in which no request completed:
    a stall shows here, whatever the median and the tail read."""
    done = sorted([0.0, float(seconds)] + [rec[0] for rec in records])
    return max(b - a for a, b in zip(done, done[1:]))


class Http:
    """The harness's own keep-alive connection (set-up and snapshots)."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)

    def __call__(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        self.conn.request(method, path, body=data, headers={"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw[:1] in (b"{", b"[") else raw.decode())


def snapshot(http: Http, since: int = 0) -> dict:
    """The program's counters and stage quantiles, as it serves them, and
    its journal's events after ``since``."""
    st, metrics = http("GET", "/metrics")
    st2, status = http("GET", "/debug/status")
    st3, journal = http("GET", f"/ops/events?since={since}&limit=256")
    if (st, st2, st3) != (200, 200, 200):
        raise RuntimeError(f"/metrics {st}, /debug/status {st2}, /ops/events {st3}")
    return {
        "metrics": metrics,
        "stages": status.get("stages") or {},
        "events": journal["events"],
        "last_seq": journal["lastSeq"],
    }


def start_children(spec: dict, tmp: Path, port: int, facts: dict, keys_path: Path, seed: int):
    """The load generator: ``processes`` children with ``clients`` keep-alive
    clients among them. Returns [(process, out_path)] once every child has
    sent its warm-up and said ``ready``."""
    traffic = spec["traffic"]
    n_clients, n_proc = int(traffic["clients"]), int(traffic["processes"])
    n_classes = len(traffic["shapes"]) * sum(1 for s in traffic["granularity"].values() if s > 0)
    keep = max(1, -(-int(traffic["check_sample"]) // (n_clients * n_classes)))
    children = []
    for p in range(n_proc):
        out_path = tmp / f"loadgen_{p}.json"
        job = {
            "seed": seed,
            "port": port,
            "traffic": traffic,
            "facts": facts,
            "keys_path": str(keys_path),
            "clients": list(range(p, n_clients, n_proc)),
            "n_clients": n_clients,
            "warm_requests_per_client": int(traffic["warm_requests_per_client"]),
            "keep_per_class": keep,
            "timeout_s": float(traffic["timeout_s"]),
            "out_path": str(out_path),
        }
        job_path = tmp / f"job_{p}.json"
        job_path.write_text(json.dumps(job))
        proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), str(job_path)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        children.append((proc, out_path))
    for proc, _ in children:
        line = proc.stdout.readline().strip()
        if line != "ready":
            stop_children(children)
            raise RuntimeError(f"load generator said {line!r}, not ready")
    return children


def stop_children(children) -> None:
    for proc, _ in children:
        if proc.poll() is None:
            proc.kill()
        proc.wait()


def traced_window(trace_dir: Path, t_from: float, seconds: float):
    """jax.profiler around ``seconds`` of the window; returns the reduced
    trace (device busy time, operations, gaps)."""
    import jax

    import trace_reduce

    time.sleep(max(0.0, t_from - time.time()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.time()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    say(f"traced {time.time() - t0:.2f}s")
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    return trace_reduce.reduce_events(trace_reduce.load_xplane(files[-1]))


class GcClock:
    """Collections of the interpreter's garbage collector that took over
    10 ms: (end on the epoch clock, seconds, generation). The server and
    the harness share one interpreter, and a full collection stops every
    request thread at once; the run line says whether a stall was one."""

    def __init__(self) -> None:
        import gc

        self.pauses: list[tuple] = []
        self._t0 = None
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            took = time.perf_counter() - self._t0
            if took > 0.010:
                self.pauses.append((time.time(), took, info["generation"]))

    def close(self) -> None:
        import gc

        gc.callbacks.remove(self)

    def within(self, t_from: float, t_to: float) -> dict:
        inside = [p for p in self.pauses if t_from <= p[0] <= t_to + p[1]]
        longest = max(inside, key=lambda p: p[1], default=(0.0, 0.0, -1))
        return {"over_10ms": len(inside), "total_s": sum(p[1] for p in inside),
                "longest_s": longest[1], "generation_of_longest": longest[2]}


class SearchTap:
    """What ``engine.search`` answered every payload it was given, kept by
    the request it came from. The envelopes of ``/g_variants`` carry no call
    count, allele count or sample name, and with INFO-sourced counts nothing
    in them depends on the samples a filter selected; so the check reads
    the window's own engine answers here, together with the samples the
    server resolved the request's filters to. The tap only keeps references:
    the timed path runs the program's own ``search``."""

    def __init__(self, engine):
        self.seen: dict[tuple, tuple] = {}
        self._search = engine.search
        engine.search = self

    def __call__(self, payload):
        responses = self._search(payload)
        self.seen[self.key_of(payload)] = (payload, responses)
        return responses

    @staticmethod
    def _key(datasets, chrom, brackets, ref, alt, vtype, min_len, max_len, granularity, include):
        import reference

        up = lambda v: v.upper() if isinstance(v, str) else None
        max_len = -1 if max_len is None or int(max_len) < 0 else int(max_len)
        return (
            tuple(sorted(datasets)), reference.chrom_code(chrom), *map(int, brackets),
            up(ref), up(alt), up(vtype), int(min_len or 0), max_len, granularity, include,
        )

    @classmethod
    def key_of(cls, p):
        return cls._key(
            p.dataset_ids, p.reference_name, (p.start_min, p.start_max, p.end_min, p.end_max),
            p.reference_bases, p.alternate_bases, p.variant_type, p.variant_min_length,
            p.variant_max_length, p.requested_granularity, p.include_datasets,
        )

    def of_request(self, datasets, q):
        """(payload, responses) of the engine call that answered the request
        ``q`` over ``datasets``, or None when no call did."""
        return self.seen.get(self._key(
            datasets, q.chrom, (q.start_min, q.start_max, q.end_min, q.end_max),
            q.ref, q.alt, q.vtype, q.min_len, q.max_len, q.granularity, q.include,
        ))


def check_answers(spec: dict, ref_shards: list, kept: list, tap: SearchTap,
                  term_shift: int = 0) -> dict:
    """Every kept answer of the window against the plain reference: the
    envelope as it was served; the samples the server resolved the
    request's filters to, against the reference's own map from term to
    samples; and what the engine answered that request inside the window
    (call and allele counts, variants, carriers among the selected
    samples). ``term_shift`` is the control's broken map: term t selects
    the samples of term t + shift. Returns counts and the first few
    differences."""
    import corpus
    import reference

    config = spec["config"]
    all_ids = corpus.dataset_ids(config)
    terms = corpus.disease_terms(config)
    position_of = {s.dataset_id: {n: i for i, n in enumerate(s.sample_names)} for s in ref_shards}
    of_term: dict[str, set] = {}

    def selected_of(q):
        """Sample positions the reference's own term map selects, sorted."""
        if not q.filter_ids:
            return None
        sel = set(range(config["n_samples"]))
        for term in q.filter_ids:
            if term not in of_term:
                shifted = terms[(terms.index(term) + term_shift) % len(terms)]
                of_term[term] = set(corpus.selected_positions(config, shifted))
            sel &= of_term[term]
        return sorted(sel)

    def against_the_window(datasets, q, selected):
        call = tap.of_request(datasets, q)
        if call is None:
            return None, "no engine call of this run carries the request"
        payload, responses = call
        if selected is None:
            if payload.selected_samples_only:
                return None, "samples selected for a request without filters"
            return responses, None
        if not payload.selected_samples_only:
            return None, "the request's filters selected no samples: the selection was dropped"
        served: dict[str, list] = {}
        for ds in datasets:
            names = payload.sample_names.get(ds) or []
            at = [position_of[ds].get(n, -1) for n in names]
            if sorted(at) != selected:
                return None, (f"{ds}: filters resolved to {len(at)} samples, "
                              f"{len(set(at) ^ set(selected))} not as the reference's {len(selected)}")
            served[ds] = at
        return responses, served

    compared = wrong = 0
    classes: dict[str, int] = {}
    diffs: list[str] = []
    for cls, path, body, raw in kept:
        q = reference.parse_body(body)
        selected = selected_of(q)
        datasets = [path.split("/")[2]] if path.startswith("/datasets/") else all_ids
        responses, served = against_the_window(datasets, q, selected)
        if responses is None:
            diff = served
        else:
            # carriers are positions in the server's own order of the selection
            want = reference.answers(
                [s for s in ref_shards if s.dataset_id in datasets], q,
                lambda s, _q: served[s.dataset_id] if served else None,
            )
            diff = reference.envelope_mismatch(json.loads(raw), reference.envelope_facts(q, want))
            if diff is None:
                diff = reference.answers_mismatch(responses, want)
        compared += 1
        classes[cls] = classes.get(cls, 0) + 1
        if diff is not None:
            wrong += 1
            if len(diffs) < 5:
                diffs.append(f"{cls}: {diff}")
    return {"compared": compared, "wrong": wrong, "classes": classes, "diffs": diffs}


def read_layers(spec: dict, ctx: dict, workload: str) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell (or
    lists none), each through the reader its layer file names. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in spec["bench"]["per_layer"]:
        if "workloads" in entry and workload not in entry["workloads"]:
            continue
        layer = load_json(spec["layers_dir"] / f"{entry['name']}.json")
        reader = load_module(HERE / "readers" / f"{layer['reader']}.py")
        value = reader.read(layer.get("args") or {}, ctx)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def run(args) -> int:
    root = Path(args.bench_root).resolve()
    spec = find_cell(root, args.workload)
    config, traffic, cell = spec["config"], spec["traffic"], spec["cell"]

    try:
        import jax

        import sbeacon_tpu  # noqa: F401
    except ImportError as e:
        say(f"cannot import the system: {e}")
        return 1
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearsal and (platform != "tpu" or len(devices) < cell["chips"]):
        say(f"needs {cell['chips']} TPU chip(s); JAX found {len(devices)} x {platform!r}")
        return 3
    if args.rehearsal and platform != "tpu":
        # drive the chip's index family on any backend, as the smoke's
        # rehearsal does: make_device_index picks the XLA family off the TPU
        import sbeacon_tpu.engine as engine_mod
        from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex

        engine_mod.make_device_index = lambda shard, **_kw: ScatterDeviceIndex(shard)

    import corpus
    import reference
    from sbeacon_tpu.api.server import build_app, start_background, warm_app
    from sbeacon_tpu.config import BeaconConfig
    from sbeacon_tpu.telemetry import flight_recorder

    stage = Stages()
    tmp = Path(tempfile.mkdtemp(prefix="bench_"))
    app = server = gc_clock = None
    children = []
    try:
        with stage("datagen"):
            shards = corpus.make_shards(config, args.seed)
            keys_path = tmp / "keys.csv"
            ref_shards = [reference.RefShard.of(s) for s in shards.values()]
            facts = corpus.write_keys(
                shards, ref_shards, traffic["keys_per_class"], args.seed, str(keys_path)
            )
            facts.update(
                assembly=config["assembly"],
                terms=corpus.disease_terms(config),
                datasets=corpus.dataset_ids(config),
            )
        with stage("build_app"):
            app, n_loaded = build_app(BeaconConfig.from_env(tmp / "beacon_root"))
            if n_loaded:
                raise RuntimeError("the data root was not empty")
        engine = app.engine
        with stage("pack_upload"):
            for shard in shards.values():
                engine.add_index(shard)
        with stage("warmup"):
            n_warm = warm_app(app)
        if engine.warmup_failed_phases:
            raise RuntimeError("a warmup phase failed")
        server, _thread = start_background(app)
        port = server.server_address[1]
        http = Http(port)
        with stage("metadata"):
            for ds, shard in shards.items():
                samples = shard.meta["sample_names"] if facts["terms"] else []
                st, doc = http("POST", "/submit", corpus.metadata_submission(config, ds, samples))
                if st != 200:
                    raise RuntimeError(f"/submit {ds}: {st} {doc}")
        tap = SearchTap(engine)
        gc_clock = GcClock()

        def window(seed: int, seconds: float, trace_it: bool, keys_file: Path) -> dict:
            """Warm-up traffic, then one measured window of the cell's mix
            drawn from ``seed``; what the children and the program recorded."""
            nonlocal children
            with stage("traffic_warm"):
                children = start_children(spec, tmp, port, facts, keys_file, seed)
            before = snapshot(http)
            fallbacks0 = sum(flight_recorder.fallbacks_by_site().values())
            t_ramp = time.time() + 0.2
            t_start = t_ramp + RAMP_S
            t_end = t_start + seconds
            for proc, _ in children:
                proc.stdin.write(f"go {t_ramp!r} {t_start!r} {t_end!r}\n")
                proc.stdin.flush()
            trace = None
            if trace_it:
                trace_s = min(TRACE_S, seconds / 2)
                trace = traced_window(tmp / "trace", t_start + (seconds - trace_s) / 2, trace_s)
            results = []
            for proc, out_path in children:
                proc.wait(timeout=seconds + float(traffic["timeout_s"]) + 60)
                if proc.returncode != 0:
                    raise RuntimeError(f"a load generator exited {proc.returncode}")
                results.append(load_json(out_path))
            children = []
            after = snapshot(http, since=before["last_seq"])
            fatal = [f for r in results for f in r["fatal"]]
            if fatal or any(r["alive"] for r in results):
                raise RuntimeError(f"load generator clients died: {fatal[:3]}")
            kept = [k for r in results for k in r["kept"]]
            kept += [[s[1], *s[2]] for r in results for s in r["slowest"]]
            return {
                "t_start": t_start, "before": before, "after": after, "trace": trace,
                "results": results, "kept": kept,
                "records": [rec for r in results for rec in r["records"]],
                "fallbacks": sum(flight_recorder.fallbacks_by_site().values()) - fallbacks0,
            }

        def judge(w: dict, control: bool) -> bool:
            """Prints every number compared beside its limit; with
            ``control``, also the same answers held to each weakened
            reference, which must come out as not correct."""
            t0 = time.perf_counter()
            verdict = check_answers(spec, ref_shards, w["kept"], tap)
            check_s = time.perf_counter() - t0
            min_checked = int(traffic["check_min"])
            print(json.dumps({
                "check": {
                    "answers_compared": verdict["compared"], "at_least": min_checked,
                    "answers_wrong": verdict["wrong"], "limit_wrong": 0,
                    "device_fallbacks_in_window": w["fallbacks"], "limit_fallbacks": 0,
                    "classes": verdict["classes"], "first_differences": verdict["diffs"],
                    "check_s": round(check_s, 2),
                }
            }), flush=True)
            if control:
                every = int(config["control"]["stale_rows_every"])
                readings = {"stale_allele_counts": check_answers(
                    spec, [reference.stale_copy(s, every, args.seed) for s in ref_shards],
                    w["kept"], tap)}
                if any(s.gt_bits is not None for s in ref_shards):
                    readings["stale_carrier_bits"] = check_answers(
                        spec, [reference.stale_planes(s, every, args.seed) for s in ref_shards],
                        w["kept"], tap)
                if facts["terms"] and (traffic.get("filters") or {}).get("share", 0) > 0:
                    readings["shifted_term_map"] = check_answers(
                        spec, ref_shards, w["kept"], tap, term_shift=1)
                print(json.dumps({"control": {
                    "stale_rows_every": every, "answers_compared": verdict["compared"],
                    "answers_wrong": {k: v["wrong"] for k, v in readings.items()},
                    "comes_out_correct": any(v["wrong"] == 0 for v in readings.values()),
                }}), flush=True)
            return (
                verdict["wrong"] == 0
                and verdict["compared"] >= min_checked
                and w["fallbacks"] == 0
            )

        w = window(args.seed, args.seconds, bool(args.trace), keys_path)
        setup_s = w["t_start"] - T_PROCESS_START
        say(f"set-up {setup_s:.1f}s; stages {json.dumps({k: round(v, 2) for k, v in stage.seconds.items()})}")
        before, after, trace, results, records = (
            w["before"], w["after"], w["trace"], w["results"], w["records"])
        ok = [rec for rec in records if rec[3] == 200]
        if not ok:
            raise RuntimeError(f"no request of the window succeeded: {len(records)} attempted")
        attempted, failed = len(records), len(records) - len(ok)
        lat = [rec[1] for rec in ok]
        by_status: dict[str, int] = {}
        for rec in records:
            by_status[str(rec[3])] = by_status.get(str(rec[3]), 0) + 1
        longest_gap_s = longest_gap(records, args.seconds)
        journal: dict[str, int] = {}
        for e in after["events"]:
            journal[e.get("kind", "?")] = journal.get(e.get("kind", "?"), 0) + 1
        correct = judge(w, bool(args.control))

        for k, seed in enumerate(args.check_seeds):
            # the limits' readings: further short windows of the same corpus
            # under other traffic seeds, each held to the reference and to
            # the controls, for one set-up (PERF.md section 2)
            more_keys = tmp / f"keys_{k}.csv"
            corpus.write_keys(shards, ref_shards, traffic["keys_per_class"], seed, str(more_keys))
            tap.seen.clear()
            wk = window(seed, args.seconds, False, more_keys)
            n_ok = sum(1 for rec in wk["records"] if rec[3] == 200)
            say(f"check seed {seed}: {n_ok} of {len(wk['records'])} requests succeeded; "
                f"longest gap {longest_gap(wk['records'], args.seconds):.2f}s; collections "
                f"{json.dumps(gc_clock.within(wk['t_start'], wk['t_start'] + args.seconds))}")
            correct = judge(wk, True) and correct

        e2e = {
            "query_p50_ms": {"value": quantile(lat, 50), "unit": "ms"},
            "query_p95_ms": {"value": quantile(lat, 95), "unit": "ms"},
            "queries_per_s": {"value": len(ok) / args.seconds, "unit": "1/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        device = {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(peak),
        }
        line = {"correct": bool(correct), "attempted": attempted, "failed": failed}
        family_launches = {
            f: n - (before["metrics"].get("device", {}).get("launches", {}) or {}).get(f, 0)
            for f, n in ((after["metrics"].get("device", {}).get("launches")) or {}).items()
        }
        counters = load_module(HERE / "readers" / "counter_ratio.py")
        print(json.dumps({
            "stages_s": {k: round(v, 3) for k, v in stage.seconds.items()},
            "programs_warmed": n_warm,
            "requests_by_status": by_status,
            "latency_max_ms": max(lat),
            "longest_gap_without_completion_s": longest_gap_s,
            "journal_events_in_window": journal,
            "launches_by_family_in_window": family_launches,
            "compiles_in_window": counters.delta(w, ["device.mid_request_compiles"]),
            "gc_pauses_in_window": gc_clock.within(w["t_start"], w["t_start"] + args.seconds),
            "client_errors": sum(r["n_errors"] for r in results),
            "first_client_errors": [e for r in results for e in r["errors"]][:3],
            "first_failures": [f for r in results for f in r["failures"]][:3],
            "end_to_end": {k: v["value"] for k, v in e2e.items()},
        }))
        if args.trace:
            peaks = load_json(HERE / "peaks.json")
            if not args.rehearsal and device["kind"] not in peaks["devices"]:
                raise RuntimeError(f"no peaks for device kind {device['kind']!r}")
            ctx = {
                "before": before, "after": after, "records": ok, "stage_s": stage.seconds,
                "trace": trace, "config": config,
                "peaks": peaks["devices"].get(device["kind"]),
                "family_launches": family_launches,
                "roofline": lambda family: load_module(HERE / "rooflines" / f"{family}.py"),
            }
            ctx["counter_delta"] = lambda paths: counters.delta(ctx, paths)
            line["metrics"] = read_layers(spec, ctx, args.workload)
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {
                "device_ops": trace["device_ops"][:10],
                "idle_gaps": trace["idle_gaps"][:10],
            }
        else:
            line["metrics"] = e2e
        line["device"] = device
        if args.rehearsal:
            line["rehearsal"] = True
        rc = 0
    finally:
        stop_children(children)
        if gc_clock is not None:
            gc_clock.close()
        if server is not None:
            server.shutdown()
            server.server_close()
        if app is not None:
            app.close()
            app.engine.close()
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--bench-root", default=str(REPO),
        help="directory holding BENCHMARK.json and its data files (default: the checkout)",
    )
    ap.add_argument(
        "--control", type=int, choices=(0, 1), default=0,
        help="also hold the window's answers to each weakened reference (each must fail)",
    )
    ap.add_argument(
        "--check-seeds", type=lambda v: [int(x) for x in v.split(",") if x], default=[],
        help="after the run, one more window per traffic seed over the same corpus, each "
             "held to the reference and the controls: the limits' readings for one set-up",
    )
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="run on whatever platform JAX finds; the line is marked and names the platform",
    )
    args = ap.parse_args(argv)
    return run(args)


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
        if rc and e.code is not None and not isinstance(e.code, int):
            print(e.code, file=sys.stderr)
    except BaseException:
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # past this point only interpreter teardown is left, and the program's
    # daemon threads can abort it (PERF.md, PR 21: "exception not rethrown")
    os._exit(rc)
