"""What the chip cannot be asked twice (ISSUE 21): where the compile
cache lives, that a failed device path is counted and still answers
right, and that ``chip_smoke.py`` rehearses to its end on the CPU and
refuses to run at full size without a chip."""

import json
import os
import random
import subprocess
import sys
import threading
import warnings
from pathlib import Path

import jax
import pytest

import sbeacon_tpu.config as config_mod
import sbeacon_tpu.engine as engine_mod
import sbeacon_tpu.telemetry as tel
from sbeacon_tpu import native
from sbeacon_tpu.config import (
    COMPILE_CACHE_DIR,
    BeaconConfig,
    EngineConfig,
    enable_persistent_compile_cache,
)
from sbeacon_tpu.engine import (
    VariantEngine,
    host_match_rows,
    materialize_response_loop,
)
from sbeacon_tpu.harness import faults
from sbeacon_tpu.index.columnar import build_index
from sbeacon_tpu.ops.kernel import QuerySpec
from sbeacon_tpu.ops.scatter_kernel import ScatterDeviceIndex
from sbeacon_tpu.payloads import VariantQueryPayload
from sbeacon_tpu.testing import random_records

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


# -- (a) compile cache placement ----------------------------------------------


@pytest.fixture
def restore_cache_dir():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_compile_cache_env_decides(monkeypatch, tmp_path, restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set the function sets no
    directory; the cache keeps every program there too."""
    jax.config.update("jax_compilation_cache_dir", "/marker/untouched")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    monkeypatch.delenv(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False
    )
    assert enable_persistent_compile_cache() == tmp_path / "c"
    assert jax.config.jax_compilation_cache_dir == "/marker/untouched"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert not (tmp_path / "c").exists()
    # ... unless the operator set JAX's own threshold variable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    enable_persistent_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_compile_cache_fixed_path_for_any_data_root(
    monkeypatch, tmp_path, restore_cache_dir
):
    from sbeacon_tpu.api.server import build_app

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert COMPILE_CACHE_DIR == REPO / ".jax_cache"
    # the same rule with the fixed place moved out of the checkout,
    # so the suite leaves nothing in the tree
    fixed = tmp_path / "fixed"
    monkeypatch.setattr(config_mod, "COMPILE_CACHE_DIR", fixed)
    assert enable_persistent_compile_cache() == fixed
    for root in ("one", "two"):
        jax.config.update("jax_compilation_cache_dir", None)
        app, n = build_app(BeaconConfig.from_env(tmp_path / root))
        try:
            assert n == 0
            assert jax.config.jax_compilation_cache_dir == str(fixed)
        finally:
            app.close()
            app.engine.close()
    assert not list(tmp_path.rglob("jax-cache"))


# -- (b) a failed device path is counted, and the answer stays right ----------


class _OwnThreadsRecorder(tel.DeviceFlightRecorder):
    """A flight recorder that records what the test's OWN threads do.

    The recorder is process-wide and knows a compile by a program key
    it sees for the first time, so planted fresh it reads the launch of
    ANY thread of the worker as a compile inside a request: a thread an
    earlier module of the same xdist worker left behind (a batcher's
    launcher, a dispatch pool, a prober: whatever was alive when the
    fixture was entered) that launches once while a test of this file
    runs makes ``mid_request_compiles`` 1 for an engine that compiled
    nothing. Those threads' records are set aside (``strays``), never
    counted; every thread started after the fixture (the engine's pools,
    its warm-up's, its builders') is the test's own and counts in full.
    """

    def __init__(self):
        super().__init__()
        # the Thread objects themselves: an ident can be handed out
        # again to a thread of the test's once its first owner has ended
        self._before = set(threading.enumerate()) - {
            threading.current_thread()
        }
        self.strays: list = []

    def _stray(self, what, family, kw) -> bool:
        me = threading.current_thread()
        if me not in self._before:
            return False
        self.strays.append((me.name, what, family, kw.get("program_key")))
        return True

    def record_launch(self, family, **kw):
        if self._stray("launch", family, kw):
            return 0  # no record carries this number: note_stage no-ops
        return super().record_launch(family, **kw)

    def record_compile(self, family, **kw):
        if not self._stray("compile", family, kw):
            super().record_compile(family, **kw)

    def record_fallback(self, site):
        if not self._stray("fallback", site, {}):
            super().record_fallback(site)


@pytest.fixture
def chip_family(monkeypatch):
    """The chip's index family on the CPU, under a fresh recorder of
    the test's own threads and with no fault plan left behind."""
    monkeypatch.setattr(
        engine_mod,
        "make_device_index",
        lambda shard, **_kw: ScatterDeviceIndex(shard),
    )
    recorder = _OwnThreadsRecorder()
    monkeypatch.setattr(tel, "flight_recorder", recorder)
    yield recorder
    faults.uninstall()
    if recorder.strays:
        # what was set aside is reported, pass or fail: the run that
        # shows a stray launch confirms what the recorder assumes, and
        # a failure with none here shows it wrong
        warnings.warn(
            f"{len(recorder.strays)} device records of threads older than "
            f"the test were set aside: {recorder.strays[:8]}"
        )


def _shard(seed=3, ds="fb"):
    rng = random.Random(seed)
    names = [f"S{i}" for i in range(9)]
    recs = random_records(
        rng, chrom="7", n=300, n_samples=9, p_multiallelic=0.3,
        p_no_acan=0.5,
    )
    return build_index(
        recs, dataset_id=ds, vcf_location=f"{ds}.vcf", sample_names=names
    )


def _payloads(shard, ds="fb"):
    pos = shard.cols["pos"]
    lo, hi = int(pos[40]), int(pos[90])
    base = dict(
        dataset_ids=[ds], reference_name="7", start_min=lo, start_max=hi,
        end_min=lo, end_max=1 << 30, alternate_bases="N",
        include_datasets="HIT", include_samples=True,
        no_response_cache=True,
    )
    return [
        VariantQueryPayload(requested_granularity="count", **base),
        VariantQueryPayload(requested_granularity="record", **base),
        VariantQueryPayload(
            requested_granularity="record",
            sample_names={ds: ["S1", "S4", "S7"]},
            selected_samples_only=True,
            **base,
        ),
    ]


def _reference(shard, payload):
    ds = payload.dataset_ids[0]
    selected = None
    if payload.selected_samples_only:
        names = shard.meta["sample_names"]
        selected = [names.index(s) for s in payload.sample_names[ds]]
    rows = host_match_rows(
        shard,
        QuerySpec(
            "7", payload.start_min, payload.start_max, payload.end_min,
            payload.end_max, None, payload.alternate_bases,
        ),
        ref_wildcard=payload.selected_samples_only,
    )
    return materialize_response_loop(
        shard, rows, payload, chrom_label="7", dataset_id=ds,
        vcf_location=shard.meta["vcf_location"], selected_idx=selected,
    )


def _assert_oracle_equal(eng, shard):
    for payload in _payloads(shard):
        (got,) = eng.search(payload)
        assert got == _reference(shard, payload)


def _engine():
    return VariantEngine(
        BeaconConfig(engine=EngineConfig(use_mesh=False, microbatch=False))
    )


def _fail_once(detail):
    faults.install(
        {"rules": [{"site": "device.bringup", "match": detail, "count": 1}]}
    )


def test_healthy_bringup_counts_no_fallback(chip_family):
    eng = _engine()
    try:
        shard = _shard()
        eng.add_index(shard)
        assert eng.warmup() > 0 and eng.warmup_failed_phases == 0
        _assert_oracle_equal(eng, shard)
        assert chip_family.fallbacks_by_site() == {}
        # the warmed programs are known to the compile tracker: no
        # first serving launch reads as a mid-request compile
        assert chip_family.mid_request_compiles() == 0
    finally:
        eng.close()


@pytest.mark.parametrize("site", ["index_build", "plane_upload"])
def test_failed_index_or_plane_build_is_counted(chip_family, site):
    eng = _engine()
    try:
        shard = _shard()
        _fail_once(site)
        eng.add_index(shard)
        _shard_, dindex, planes = eng._indexes[("fb", "fb.vcf")]
        assert (dindex if site == "index_build" else planes) is None
        assert chip_family.fallbacks_by_site() == {site: 1}
        _assert_oracle_equal(eng, shard)
    finally:
        eng.close()


def test_failed_warmup_phase_is_counted(chip_family):
    eng = _engine()
    try:
        eng.add_index(_shard(3, "fb"))
        eng.add_index(_shard(4, "fc"))
        _fail_once("warmup_fused")
        assert eng.warmup() > 0
        assert eng.warmup_failed_phases == 1
        assert chip_family.fallbacks_by_site() == {"warmup_fused": 1}
        # the next run is clean, and says so
        eng.warmup()
        assert eng.warmup_failed_phases == 0
    finally:
        eng.close()


def test_failed_fused_selected_is_counted_and_exposed(chip_family):
    from sbeacon_tpu.telemetry import MetricsRegistry, register_device_metrics

    eng = _engine()
    try:
        shard = _shard()
        eng.add_index(shard)
        _fail_once("fused_selected")
        _assert_oracle_equal(eng, shard)
        assert chip_family.fallbacks_by_site() == {"fused_selected": 1}
        reg = MetricsRegistry()
        register_device_metrics(reg)
        assert reg.render_json()["device"]["fallbacks"] == {
            "fused_selected": 1
        }
        assert chip_family.snapshot()["fallbacks"] == {"fused_selected": 1}
    finally:
        eng.close()


def _payload_over(shards: dict):
    """One count query over the same window of several datasets."""
    pos = next(iter(shards.values())).cols["pos"]
    return VariantQueryPayload(
        dataset_ids=sorted(shards), reference_name="7",
        start_min=int(pos[40]), start_max=int(pos[90]),
        end_min=int(pos[40]), end_max=1 << 30, alternate_bases="N",
        requested_granularity="count", include_datasets="HIT",
        no_response_cache=True,
    )


def test_a_warmed_engine_compiles_what_it_publishes_before_serving_it(
    chip_family, monkeypatch,
):
    """After warmup() the engine is serving: a later base publish (a
    /submit, a compactor fold) warms the index, the launch group it
    joins on its owner chip (the fused match+planes program over ALL
    the chip's plane datasets) and the fused stack that now covers it,
    on the publishing thread. No request, and no canary probe, pays
    the compile."""
    # one chip owns every dataset, so a later publish joins a group
    monkeypatch.setattr(jax, "local_devices", lambda: jax.devices()[:1])
    eng = _engine()
    try:
        shards = {"fb": _shard(3, "fb")}
        eng.add_index(shards["fb"])
        eng.warmup()
        for seed, ds in ((4, "fc"), (5, "fd")):
            late = shards[ds] = _shard(seed, ds)
            eng.add_index(late)
            (got,) = eng.search(_payloads(late, ds)[1])
            assert got == _reference(late, _payloads(late, ds)[1])
            # a record request with sample extraction over everything
            # published: ONE launch of the chip's group as it now is
            (group,) = {g for g, _slot in eng._plane_groups.values()}
            assert [k[0] for k, _d, _p in group] == list(shards)
            over = _payloads(late, ds)[1]
            over.dataset_ids = sorted(shards)
            launches = chip_family.launches_by_family()["plane"]
            got = eng.search(over)
            assert chip_family.launches_by_family()["plane"] == launches + 1
            for r, (name, shard) in zip(got, sorted(shards.items())):
                one = _payloads(late, name)[1]
                assert r == _reference(shard, one)
            assert chip_family.mid_request_compiles() == 0, (
                chip_family.last_mid_request_compile()
            )
        # both later datasets ride the rebuilt, warmed fused stack
        before = eng.fused_searches
        assert len(eng.search(
            _payload_over({"fb": shards["fb"], "fd": late})
        )) == 2
        assert eng.fused_searches == before + 1
        assert chip_family.mid_request_compiles() == 0, (
            chip_family.last_mid_request_compile()
        )
        assert chip_family.fallbacks_by_site() == {}
        assert eng.warmup_failed_phases == 0
    finally:
        eng.close()


def test_a_failed_warm_at_publish_is_counted_and_the_index_serves(
    chip_family,
):
    eng = _engine()
    try:
        eng.add_index(_shard(3, "fb"))
        eng.warmup()
        late = _shard(4, "fc")
        _fail_once("warmup_scatter")
        eng.add_index(late)
        assert chip_family.fallbacks_by_site() == {"warmup_scatter": 1}
        assert eng.warmup_failed_phases == 1
        for payload in _payloads(late, "fc"):
            (got,) = eng.search(payload)
            assert got == _reference(late, payload)
    finally:
        eng.close()


def test_native_library_is_named_by_its_sources():
    """A copied or older ``_sbnative.so`` is never the file loaded."""
    stale = native._DIR / "_sbnative.so"
    stale.write_bytes(b"not a library")
    path = native.build()
    assert path.name == f"_sbnative.{native.source_hash()}.so"
    assert path != stale and path.stat().st_size > 1000
    assert native.available()
    stale.unlink(missing_ok=True)


# -- (d) the smoke itself -----------------------------------------------------


def _smoke_env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one virtual device: the rehearsal of the one-chip run
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    # CPU programs stay out of the checkout's cache, which travels
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return env


def test_chip_smoke_without_a_chip_fails_fast_and_prints_no_result(tmp_path):
    done = subprocess.run(
        [sys.executable, str(SMOKE), "--out", str(tmp_path / "out")],
        env=_smoke_env(tmp_path), cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert done.returncode not in (0, 2, 3)
    assert done.stdout == ""
    assert "no TPU" in done.stderr
    assert not (tmp_path / "out").exists()


@pytest.mark.timeout(300)
def test_chip_smoke_rehearsal_runs_to_its_end_on_the_cpu(tmp_path):
    done = subprocess.run(
        [sys.executable, str(SMOKE), "--rehearsal",
         "--scratch", str(tmp_path / "data"), "--out", str(tmp_path / "out")],
        env=_smoke_env(tmp_path), cwd=REPO, capture_output=True, text=True,
        timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    summary_line, verdict_line = done.stdout.strip().splitlines()[-2:]
    doc = json.loads(summary_line)
    # the last line is the verdict alone, exactly these keys
    assert json.loads(verdict_line) == {"ok": doc["ok"], "device": doc["device"]}
    assert doc == json.loads(
        (tmp_path / "out" / "chip_smoke_rehearsal.json").read_text()
    )
    assert doc["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # a rehearsal never prints the success line of a chip run
    assert doc["ok"] is False and doc["rehearsal_passed"] is True
    n, of = map(int, doc["parity"].split("/"))
    assert n == of >= 40
    assert doc["requests"]["failed"] == 0
    assert doc["mid_request_compiles"] == 0
    assert doc["fallbacks"] == {
        "device.fallbacks": {},
        "ingest.native_fallbacks": 0,
    }
    for family in ("scatter", "plane", "fused", "fused_l0"):
        assert doc["launches"][family] > 0
    # a delta tail was read through the L0 index while it stood, and
    # again as the base the compactor folded it into
    tail = doc["delta_tail"]
    assert tail["parity_standing"] == tail["parity_folded"] == "6/6"
    assert tail["l0_served_queries"] >= 6
    assert doc["engine"]["l0_builds"] > 0
