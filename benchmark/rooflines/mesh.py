"""The engine's mesh-stack program (``parallel/mesh.py`` ``_local_query``
under ``jax.shard_map``): one launch answers a query against every dataset
of the stack, each chip its own datasets by ``ops/kernel._query_one`` (the
fused family's predicate: a bisection and a windowed read a pair), and
five ``psum``s fan the cross-dataset counts in.

The trace shows a launch of a four-chip program once a chip, and
``trace_reduce.py`` sums launches and seconds over the chips' planes. So
the least bytes here are ONE CHIP's share of a launch: the pairs that chip
evaluates (the stack's dataset slots over the chips, times the batch: 32
in ``mds4.fanout``), each by ``rooflines/fused.py``'s own convention, at
least 256 rows (``window_hint_for``'s floor) of the 11 int32 device
columns; the bisection's reads, the all-reduces and the outputs are left
out, so the share errs low. Pairs per launch are the program's own
``device.evaluated_pairs`` over the family's launches in the measured
window; a program that does not record the family (this file laid over an
older checkout) has launched once a request over every dataset of the
configuration. By hand, ``mds4.fanout``: 32 pairs x 256 rows x 11 columns
x 4 B = 360,448 B a chip a launch; at 819 GB/s that is 0.44 us, against a
launch of 1.108 ms on the chip (PERF.md 6, PR 34): 0.040 %, as the cell
read. Every launch is latency.
"""

MODULES = [r"^jit__local_query$"]
MIN_WINDOW_ROWS = 256
DEVICE_COLUMNS = 11


def least_bytes_per_launch(ctx) -> float:
    chips = max(1, int((ctx.get("trace") or {}).get("devices") or 1))
    launches = ctx["family_launches"].get("mesh", 0)
    if launches > 0:
        pairs = ctx["counter_delta"](["device.evaluated_pairs"]) / launches
    else:
        datasets = int(ctx["config"]["datasets"])
        pairs = -(-datasets // chips) * chips
    return max(1.0, pairs / chips) * MIN_WINDOW_ROWS * DEVICE_COLUMNS * 4
