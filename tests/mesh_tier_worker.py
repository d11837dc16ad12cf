"""Subprocess body for the coordinator's single-launch contract.

A pristine process (own XLA_FLAGS-forced device count, zero prior
launches) builds a 4-dataset local engine on its defaults plus one HTTP
worker, drives an all-local boolean query through the coordinator, and
reports the contract observations as JSON: exactly ONE kernel launch
across every kernel family, of the family ``mesh`` (the engine's own
mesh program), ZERO coordinator->worker HTTP calls (the pooled
transport's process-wide stats unchanged), and per-response parity
with a plain engine that has no mesh stack. The parent test
(``test_mesh_dispatch.py::test_pod_contract_in_subprocess``) asserts
the JSON.
"""

import dataclasses
import json
import os
import sys


def main() -> None:
    out_path = sys.argv[1]

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import random

    import sbeacon_tpu.ops.kernel as kernel_mod
    from sbeacon_tpu.config import BeaconConfig, EngineConfig
    from sbeacon_tpu.engine import VariantEngine
    from sbeacon_tpu.index.columnar import build_index
    from sbeacon_tpu.ops import scatter_kernel
    from sbeacon_tpu.parallel import mesh as mesh_mod
    from sbeacon_tpu.parallel import transport as transport_mod
    from sbeacon_tpu.parallel.dispatch import DistributedEngine, WorkerServer
    from sbeacon_tpu.payloads import VariantQueryPayload
    from sbeacon_tpu.telemetry import flight_recorder
    from sbeacon_tpu.testing import random_records

    def _dicts(responses) -> list:
        return [dataclasses.asdict(r) for r in responses]

    def launches() -> int:
        return (
            kernel_mod.N_LAUNCHES
            + scatter_kernel.N_DISPATCHES
            + mesh_mod.N_LAUNCHES
        )

    def shard(d: int, rows: int = 250):
        rng = random.Random(40 + d)
        return build_index(
            random_records(rng, chrom="1", n=rows, n_samples=2),
            dataset_id=f"d{d}",
            vcf_location=f"v{d}",
            sample_names=["S0", "S1"],
        )

    def engine(shards, **over):
        eng = VariantEngine(
            BeaconConfig(
                engine=EngineConfig(response_cache=False, **over)
            )
        )
        for s in shards:
            eng.add_index(s)
        return eng

    n_shards = 4
    # the local engine on its defaults: with several devices visible
    # its own mesh stack answers a multi-dataset boolean in one launch
    eng = engine([shard(d) for d in range(n_shards)], microbatch_wait_ms=0.0)
    # one real HTTP worker in the fleet: the contract is that an
    # all-local query never touches it (its dataset is not in the query)
    weng = engine([shard(9)], use_mesh=False, microbatch=False)
    worker = WorkerServer(weng).start_background()
    dist = DistributedEngine([worker.address], local=eng)
    ref = engine(
        [shard(d) for d in range(n_shards)],
        use_mesh=False,
        microbatch=False,
    )

    def payload(gran="boolean", include="NONE"):
        return VariantQueryPayload(
            dataset_ids=[f"d{d}" for d in range(n_shards)],
            reference_name="1",
            start_min=1,
            start_max=1 << 29,
            end_min=1,
            end_max=1 << 30,
            alternate_bases="N",
            requested_granularity=gran,
            include_datasets=include,
        )

    doc = {"devices": len(jax.devices())}
    try:
        dist.replica_table()  # discovery rides HTTP once, OUTSIDE the probe
        dist.warmup()  # compiles outside the measured window

        def transport_snapshot() -> dict:
            keys = ("opened", "reused", "evicted", "retried", "gzip_bodies",
                    "hedges")
            return {k: transport_mod._STATS.get(k) for k in keys}

        t0 = transport_snapshot()
        n0 = launches()
        m0 = mesh_mod.N_LAUNCHES
        f0 = flight_recorder.launches_by_family()
        s0 = eng.mesh_searches
        got = dist.search(payload())
        doc["total_launches"] = launches() - n0
        doc["mesh_launches"] = mesh_mod.N_LAUNCHES - m0
        f1 = flight_recorder.launches_by_family()
        doc["launches_by_family"] = {
            f: f1[f] - f0.get(f, 0) for f in f1 if f1[f] != f0.get(f, 0)
        }
        t1 = transport_snapshot()
        doc["transport_stats_unchanged"] = t0 == t1
        doc["worker_http_calls"] = (t1["opened"] + t1["reused"]) - (
            t0["opened"] + t0["reused"]
        )
        doc["mesh_searches"] = eng.mesh_searches - s0
        doc["exists"] = any(r.exists for r in got)

        # parity: every shape against a plain engine with no mesh stack
        parity = _dicts(got) == _dicts(ref.search(payload()))
        for gran, include in [
            ("count", "HIT"), ("record", "HIT"), ("aggregated", "ALL"),
        ]:
            parity = parity and _dicts(
                dist.search(payload(gran, include))
            ) == _dicts(ref.search(payload(gran, include)))
        doc["parity_ok"] = parity
    finally:
        dist.close()
        worker.shutdown()
        eng.close()
        weng.close()
        ref.close()

    with open(out_path, "w") as fh:
        json.dump(doc, fh)
    print("mesh tier worker OK", flush=True)


if __name__ == "__main__":
    main()
