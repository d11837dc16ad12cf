"""The general traffic generator and the load-generating child process.

Standard library only: the children never import JAX, so the parent keeps
the chip and the clients do not share the server's interpreter lock.

A traffic mix is a data file (``benchmark/traffic/<name>.json``). Every
request is a pure function of ``(seed, key class, key index)``: the same
key always gives the same request, so drawing keys without replacement
gives requests that are all distinct, and drawing them from a hot set
gives repeats the response cache can serve.

As a child: ``python3 benchmark/loadgen.py <job.json>`` reads its job,
opens its keep-alive clients, sends the unmeasured warm-up, prints
``ready``, waits for ``go <ramp> <start> <end>`` (epoch seconds) on stdin,
drives the load, and writes its results file.
"""

from __future__ import annotations

import csv
import http.client
import json
import random
import socket
import sys
import threading
import time

SV_TYPE_OF = {"<DEL>": "DEL", "<DUP>": "DUP", "<INS>": "INS", "<CN0>": "DEL", "<CN2>": "DUP:TANDEM"}
BASES = "ACGT"
#: keys per client at the far end of each table that only the warm-up may use
WARM_RESERVE = 64


def load_keys(path: str) -> dict:
    """{class: [(chrom, pos, ref, alt), ...]} from the key table the parent
    drew from the corpus."""
    out: dict[str, list] = {}
    with open(path, newline="") as fh:
        for cls, chrom, pos, ref, alt in csv.reader(fh):
            out.setdefault(cls, []).append((chrom, int(pos), ref, alt))
    return out


def _pick(rng: random.Random, shares: dict) -> str:
    """One name of ``{name: share}``, shares in any unit."""
    names = [n for n, s in shares.items() if s > 0]
    return rng.choices(names, [shares[n] for n in names])[0]


def _width(rng: random.Random, bounds) -> int:
    return rng.randint(int(bounds[0]), int(bounds[1]))


def request_for(traffic: dict, facts: dict, keys: dict, seed: int, shape: dict, index: int):
    """(class name, path, body) of the ``index``-th key of ``shape``'s key
    class. Deterministic in its arguments."""
    pool = keys[shape["keys"]]
    chrom, pos, ref, alt = pool[index % len(pool)]
    rng = random.Random(f"{seed}:{shape['name']}:{index}")
    hit = rng.random() < traffic["hit_share"]
    gran = _pick(rng, traffic["granularity"])
    rp = {"assemblyId": facts["assembly"], "referenceName": chrom}
    form = shape["form"]
    if form == "point":
        if hit:
            rp.update(start=[pos - 1], end=[pos + len(ref) - 1], referenceBases=ref, alternateBases=alt)
        else:
            p = min(pos + rng.randint(1, 50), facts["chrom_lengths"][chrom])
            r = rng.choice(BASES)
            rp.update(start=[p - 1], end=[p], referenceBases=r,
                      alternateBases=rng.choice(BASES.replace(r, "")))
    elif form == "range":
        width = _width(rng, shape["width_bp"])
        anchor = pos if hit else rng.randint(1, facts["chrom_lengths"][chrom])
        lo = max(1, anchor - rng.randint(0, width))
        rp.update(start=[lo - 1], end=[lo - 1 + width])
        what = _pick(rng, shape["alt"])
        if what == "N":
            rp["alternateBases"] = "N"
        else:
            rp["variantType"] = what
    elif form == "bracket":
        fuzz = _width(rng, shape["fuzz_bp"])
        vtype = SV_TYPE_OF.get(alt) or ("DEL" if len(alt) < len(ref) else "INS")
        anchor = pos if hit else rng.randint(1, facts["chrom_lengths"][chrom])
        end = anchor + len(ref) - 1
        rp.update(
            start=[max(0, anchor - 1 - rng.randint(0, fuzz)), anchor - 1 + rng.randint(0, fuzz)],
            end=[max(0, end - 1 - rng.randint(0, fuzz)), end - 1 + rng.randint(0, fuzz)],
            variantType=vtype,
        )
    elif form == "length":
        width = _width(rng, shape["width_bp"])
        lo = max(1, pos - rng.randint(0, width))
        rp.update(start=[lo - 1], end=[lo - 1 + width])
        rp["variantType"] = "DEL" if len(alt) < len(ref) else "INS"
        if hit:
            rp.update(variantMinLength=max(0, len(alt) - rng.randint(0, 2)),
                      variantMaxLength=len(alt) + rng.randint(0, 2))
        else:
            rp.update(variantMinLength=shape["miss_length"][0],
                      variantMaxLength=shape["miss_length"][1])
    else:
        raise ValueError(f"unknown shape form {form!r}")
    query = {
        "requestedGranularity": gran,
        "includeResultsetResponses": traffic["include"],
        "requestParameters": rp,
    }
    if gran == "record":
        query["pagination"] = {"skip": 0, "limit": _width(rng, traffic["record_limit"])}
    flt = traffic.get("filters") or {}
    if flt.get("share", 0) > 0 and rng.random() < flt["share"]:
        query["filters"] = [
            {"id": rng.choice(facts["terms"]), "scope": flt["scope"],
             "includeDescendantTerms": bool(flt.get("include_descendants", True))}
            for _ in range(int(flt.get("terms_per_request", 1)))
        ]
    path = "/g_variants"
    if traffic.get("fanout", "all") == "one":
        path = f"/datasets/{rng.choice(facts['datasets'])}/g_variants"
    return f"{shape['name']}.{gran}", path, {"query": query}


class KeyDraw:
    """Which key a client uses next, per shape: without replacement in the
    client's own stride, or, for the ``reuse`` share, from a hot set by a
    Zipf law (repeats)."""

    def __init__(self, traffic: dict, keys: dict, seed: int, client: int, n_clients: int, *, warm: bool):
        self.keys = keys
        self.rng = random.Random(f"{seed}:client:{client}:{int(warm)}")
        self.client, self.n_clients, self.warm = client, n_clients, warm
        self.used = {s["name"]: 0 for s in traffic["shapes"]}
        reuse = traffic.get("reuse") or {}
        self.hot_share = float(reuse.get("hot_share", 0))
        self.hot_keys = int(reuse.get("hot_keys", 0))
        if self.hot_share > 0:
            z = float(reuse.get("zipf", 0))
            self.hot_weights = [1.0 / (r + 1) ** z for r in range(self.hot_keys)]
        self.shape_of = {s["name"]: s for s in traffic["shapes"]}
        self.shares = {s["name"]: s["share"] for s in traffic["shapes"]}

    def next(self):
        """(shape, key index)."""
        shape = self.shape_of[_pick(self.rng, self.shares)]
        n = len(self.keys[shape["keys"]])
        if self.hot_share > 0 and self.rng.random() < self.hot_share:
            return shape, self.rng.choices(range(self.hot_keys), self.hot_weights)[0]
        j = self.used[shape["name"]]
        self.used[shape["name"]] = j + 1
        stride = self.client + j * self.n_clients
        if self.warm:
            # from the far end, so that no measured request repeats a warm one
            return shape, n - 1 - stride
        index = self.hot_keys + stride
        if index >= n - WARM_RESERVE * self.n_clients:
            raise RuntimeError(f"key table of {shape['keys']!r} exhausted ({n} keys)")
        return shape, index


def server_ms_of(raw: bytes) -> float:
    """``meta.elapsedTimeMs`` of an envelope, without parsing it all."""
    at = raw.rfind(b'"elapsedTimeMs": ')
    if at < 0:
        return -1.0
    end = at + 17
    while end < len(raw) and raw[end] in b"0123456789.":
        end += 1
    try:
        return float(raw[at + 17 : end])
    except ValueError:
        return -1.0


class Client(threading.Thread):
    """One keep-alive connection (copied from ``harness.latency.Client``:
    a fresh TCP connection per request makes the server spawn a thread per
    request, not per client)."""

    def __init__(self, job: dict, keys: dict, client: int):
        super().__init__(daemon=True)
        self.job, self.keys, self.client = job, keys, client
        self.conn = None
        self.records: list = []  # [t_done_rel, latency_ms, server_ms, status, class, late_ms]
        self.kept: dict[str, list] = {}  # class -> reservoir of [index, body, response]
        self.seen: dict[str, int] = {}
        self.slowest = None
        self.errors: list[str] = []
        self.fatal = None
        self.failures: list = []
        self.sample_rng = random.Random(f"{job['seed']}:sample:{client}")
        self.go = threading.Event()
        self.window = (0.0, 0.0, 0.0)

    def _send(self, path: str, body: dict):
        data = json.dumps(body).encode()
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.job["port"], timeout=self.job["timeout_s"]
                )
            try:
                self.conn.request("POST", path, body=data, headers={"Content-Type": "application/json"})
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except socket.timeout:
                # the server may be executing it: a replay would double-submit
                self.conn = None
                raise
            except (http.client.HTTPException, OSError):
                self.conn = None  # stale keep-alive: replay once, fresh
                if attempt:
                    raise

    def _one(self, draw: KeyDraw):
        job = self.job
        shape, index = draw.next()
        cls, path, body = request_for(job["traffic"], job["facts"], self.keys, job["seed"], shape, index)
        t0 = time.time()
        try:
            status, raw = self._send(path, body)
        except (OSError, http.client.HTTPException) as e:
            self.errors.append(f"{type(e).__name__}: {e}")
            status, raw = 0, b""
        return cls, body, path, t0, time.time(), status, raw

    def warm(self) -> None:
        draw = KeyDraw(self.job["traffic"], self.keys, self.job["seed"], self.client,
                       self.job["n_clients"], warm=True)
        for _ in range(self.job["warm_requests_per_client"]):
            self._one(draw)

    def run(self) -> None:
        self.go.wait()
        try:
            self._drive()
        except Exception as e:  # a dead client must show, not just fall silent
            self.fatal = f"{type(e).__name__}: {e}"
        if self.conn is not None:
            self.conn.close()

    def _drive(self) -> None:
        ramp, start, end = self.window
        job = self.job
        draw = KeyDraw(job["traffic"], self.keys, job["seed"], self.client, job["n_clients"], warm=False)
        arrival = job["traffic"]["arrival"]
        open_loop = arrival["mode"] == "open"
        arrivals = random.Random(f"{job['seed']}:arrivals:{self.client}")
        due = ramp
        keep = job["keep_per_class"]
        while True:
            now = time.time()
            if open_loop:
                # this client's share of a Poisson stream, faster in a burst
                rate = arrival["rate_per_s"] / job["n_clients"]
                every = arrival.get("burst_every_s", 0)
                if every and (due - start) % every < arrival.get("burst_len_s", 0):
                    rate *= arrival.get("burst_factor", 1)
                due += arrivals.expovariate(rate)
                if due > now:
                    time.sleep(due - now)
            elif now < ramp:
                time.sleep(ramp - now)
            if max(due if open_loop else 0.0, time.time()) >= end:
                break
            cls, body, path, t0, t1, status, raw = self._one(draw)
            if not (start <= t1 <= end):
                continue  # the ramp, or finished after the window closed
            t_ref = due if open_loop else t0
            lat = (t1 - t_ref) * 1e3
            self.records.append(
                [round(t1 - start, 6), lat, server_ms_of(raw) if status == 200 else -1.0,
                 status, cls, (t0 - due) * 1e3 if open_loop else 0.0]
            )
            if status != 200:
                if len(self.failures) < 3:
                    self.failures.append([status, raw[:300].decode("utf-8", "replace")])
                continue
            # a uniform sample of this class's answers, for the check
            n = self.seen[cls] = self.seen.get(cls, 0) + 1
            pool = self.kept.setdefault(cls, [])
            item = [path, body, raw.decode("utf-8", "replace")]
            if len(pool) < keep:
                pool.append(item)
            else:
                k = self.sample_rng.randrange(n)
                if k < keep:
                    pool[k] = item
            if self.slowest is None or lat > self.slowest[0]:
                self.slowest = [lat, cls, item]
        if self.conn is not None:
            self.conn.close()


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        job = json.load(fh)
    keys = load_keys(job["keys_path"])
    clients = [Client(job, keys, c) for c in job["clients"]]
    warmers = [threading.Thread(target=c.warm, daemon=True) for c in clients]
    for w in warmers:
        w.start()
    for w in warmers:
        w.join()
    for c in clients:
        c.start()
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 4 or line[0] != "go":
        return 2
    window = tuple(float(x) for x in line[1:])
    for c in clients:
        c.window = window
        c.go.set()
    for c in clients:
        c.join(timeout=window[2] - time.time() + job["timeout_s"] + 5)
    out = {
        "alive": sum(c.is_alive() for c in clients),
        "fatal": [c.fatal for c in clients if c.fatal],
        "records": [r for c in clients for r in c.records],
        "kept": [[cls, *item] for c in clients for cls, pool in c.kept.items() for item in pool],
        "slowest": [c.slowest for c in clients if c.slowest],
        "errors": [e for c in clients for e in c.errors][:20],
        "failures": [f for c in clients for f in c.failures][:5],
        "n_errors": sum(len(c.errors) for c in clients),
    }
    with open(job["out_path"], "w") as fh:
        json.dump(out, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
