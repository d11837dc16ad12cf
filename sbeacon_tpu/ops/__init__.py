from .kernel import (
    DeviceIndex,
    FusedDeviceIndex,
    L0DeviceIndex,
    QueryResults,
    QuerySpec,
    ReadyQueryResults,
    encode_queries,
    padded_batch,
    run_queries,
)
from .scatter_kernel import (
    ScatterDeviceIndex,
    chunk_slots,
    run_queries_scattered,
)


def make_device_index(
    shard,
    *,
    window: int | None = None,
    pad_unit: int | None = None,
    device=None,
):
    """Device index for serving: the scattered C-tile gather kernel on
    real TPU backends, the XLA gather kernel elsewhere.

    The scattered kernel replaced the round-2 grouped Pallas kernel as
    the serving default: at 1000-Genomes scale (2e7 rows) sparse
    queries leave one real query per 64-slot tile group, so the grouped
    kernel gathered and evaluated a whole tile span per query. The
    reasoning is in the module docstring of ``ops/scatter_kernel.py``;
    the rates once quoted here were taken before PR 1 on a machine that
    no longer exists and have not been re-measured (PERF.md). Real
    corpora are 2e7-scale, which decides the default. ``window`` only
    sizes the XLA fallback index; the scattered kernel applies the
    engine's window_cap per BATCH (tier split in
    run_queries_scattered), so the index needs no build-time
    window. ``device`` is the owner chip the scattered index commits
    its tiles to (the XLA fallback is not placed: its columns lie on
    the default device)."""
    import jax

    if jax.default_backend() == "tpu":
        return ScatterDeviceIndex(shard, device=device)
    return DeviceIndex(shard, pad_unit=pad_unit)


def run_queries_auto(
    index,
    queries,
    *,
    window_cap: int = 2048,
    record_cap: int = 1024,
    async_fetch: bool = False,
):
    """Dispatch a query batch to whichever kernel the index was built
    for — one call site for the engine and the micro-batcher.

    ``async_fetch=True`` returns an object with ``.fetch() ->
    QueryResults`` immediately after the launch is dispatched so the
    caller can overlap host work with device execution (the scatter
    tile kernels execute synchronously and return already-fetched
    results behind the same contract)."""
    if isinstance(index, ScatterDeviceIndex):
        res = run_queries_scattered(
            index, queries, window_cap=window_cap, record_cap=record_cap
        )
        return ReadyQueryResults(res) if async_fetch else res
    return run_queries(
        index,
        queries,
        window_cap=window_cap,
        record_cap=record_cap,
        async_fetch=async_fetch,
    )


def launch_capacity(index, n_specs: int) -> int:
    """How many query specs ride a launch whose first entry brings
    ``n_specs``, at the padded shape that entry alone already pays for:
    the scattered kernel's chunk (every tier of the batch pads to whole
    chunks of it), else the batch-ladder rung ``run_queries`` pads to.
    The same per-family choice ``run_queries_auto``
    makes, asked before the launch: the micro-batcher fills a launch
    this far and no further, so a batched launch costs the device what
    a launch of its head entry costs and runs a shape warm-up
    compiled."""
    if isinstance(index, ScatterDeviceIndex):
        slots = chunk_slots(n_specs)
        return -(-n_specs // slots) * slots
    return padded_batch(index, n_specs)


__all__ = [
    "DeviceIndex",
    "FusedDeviceIndex",
    "L0DeviceIndex",
    "QueryResults",
    "QuerySpec",
    "ReadyQueryResults",
    "encode_queries",
    "launch_capacity",
    "make_device_index",
    "run_queries",
    "run_queries_auto",
]
