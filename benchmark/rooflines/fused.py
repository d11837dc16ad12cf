"""The XLA gather program over the fused stack (``ops/kernel.py``
``_query_batch_impl``): one launch answers a batch of (query, dataset)
pairs, each by a bisection and a windowed gather over the stacked columns.

Least bytes of one launch: every evaluated pair gathers a window of at
least 256 rows (``window_hint_for``'s floor) of the 11 int32 device
columns; the bisection's reads and the outputs are left out, so the share
errs low. Pairs per launch are the program's own ``device.evaluated_pairs``
over the family's launches in the measured window.
"""

MODULES = [r"^jit__query_batch_impl$"]
MIN_WINDOW_ROWS = 256
DEVICE_COLUMNS = 11


def least_bytes_per_launch(ctx) -> float:
    launches = ctx["family_launches"].get("fused", 0)
    pairs = ctx["counter_delta"](["device.evaluated_pairs"]) / launches if launches > 0 else 1.0
    return max(1.0, pairs) * MIN_WINDOW_ROWS * DEVICE_COLUMNS * 4
