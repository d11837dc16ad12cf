"""Simulation + latency harness: populate through the real /submit path,
then walk every endpoint over live HTTP."""

import pytest

from sbeacon_tpu.api import BeaconApp
from sbeacon_tpu.api.server import start_background
from sbeacon_tpu.config import BeaconConfig, StorageConfig
from sbeacon_tpu.harness import populate, run_latency_suite


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    root = tmp_path_factory.mktemp("sim")
    config = BeaconConfig(storage=StorageConfig(root=root / "data"))
    config.storage.ensure()
    app = BeaconApp(config)
    recs = populate(
        app,
        root / "vcfs",
        n_datasets=2,
        n_individuals=5,
        records_per_chrom=150,
    )
    server, _ = start_background(app)
    url = f"http://127.0.0.1:{server.server_address[1]}"
    yield app, url, recs
    server.shutdown()
    server.server_close()


def test_populate_created_everything(live):
    app, _, recs = live
    assert set(recs) == {"sim0", "sim1"}
    assert app.store.count("datasets") == 2
    assert app.store.count("individuals") == 10
    assert app.store.count("analyses") == 10
    assert len(app.engine.datasets()) == 2
    job = app.ingest.ledger.dataset_job("sim1")
    assert job["state"] == "complete"
    assert job["variant_count"] > 0


def test_latency_suite_all_green(live):
    _, url, _ = live
    results = run_latency_suite(url, reps=2)
    # every check ran and returned a sane latency
    assert len(results) >= 18
    assert all(0 <= t < 30 for t in results.values())


def test_metadata_scale_harness_small(tmp_path):
    """The 1M-individual harness at toy scale: bulk path seeds linked
    entities the filter compiler can see (sex filter, ontology-expanded
    phenotype, cross-entity joins) through the real route handlers."""
    from sbeacon_tpu.harness.scale import run_metadata_scale

    rep = run_metadata_scale(tmp_path, n_datasets=5, individuals_per=30)
    assert rep["populate"]["individuals"] == 150
    assert rep["relations_rows"] >= 150
    # the ontology-expanded count must actually match individuals
    assert rep["queries"]["ontology_count_result"] > 0
    for key in (
        "individuals_sex_boolean",
        "individuals_sex_count",
        "individuals_sex_record",
        "individuals_ontology_count",
        "dataset_individuals_record",
    ):
        assert rep["queries"][key]["p50_ms"] > 0


def test_every_lazy_harness_name_resolves():
    """``harness._LAZY`` is a PEP 562 table: a name whose function was
    deleted beside it fails only when someone asks for it."""
    from sbeacon_tpu import harness

    for name, module in harness._LAZY.items():
        fn = getattr(harness, name)
        assert callable(fn), name
        assert fn.__module__ == f"sbeacon_tpu.harness.{module}", name
    assert set(harness.__all__) == {"faults", *harness._LAZY}
    with pytest.raises(AttributeError):
        harness.no_such_name


@pytest.fixture
def counting_server():
    """A keep-alive stdlib server that counts accepted connections;
    ``GET /drop`` answers, then closes the socket without saying so."""
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    accepted = []

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def setup(self):
            accepted.append(self.client_address)
            super().setup()

        def do_GET(self):
            body = json.dumps({"path": self.path}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            if self.path == "/drop":
                self.close_connection = True

        def log_message(self, *args):
            pass

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        yield f"http://127.0.0.1:{server.server_address[1]}", accepted
    finally:
        server.shutdown()
        server.server_close()
        thread.join(5)


def test_client_sends_every_request_over_one_connection(counting_server):
    from sbeacon_tpu.harness.latency import Client

    url, accepted = counting_server
    c = Client(url)
    for k in range(5):
        assert c.get(f"/r{k}") == (200, {"path": f"/r{k}"})
    assert c.get("/q", {"a": 1}) == (200, {"path": "/q?a=1"})
    assert len(accepted) == 1


def test_client_reopens_a_connection_the_server_closed(counting_server):
    """A stale keep-alive socket is replayed once on a fresh
    connection, not surfaced to the caller as an error."""
    from sbeacon_tpu.harness.latency import Client

    url, accepted = counting_server
    c = Client(url)
    assert c.get("/drop")[0] == 200
    assert c.get("/after") == (200, {"path": "/after"})
    assert c.get("/again")[0] == 200
    assert len(accepted) == 2
