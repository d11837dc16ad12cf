"""The scatter-tile match program (``ops/scatter_kernel.py`` ``_scatter_batch``).

Least bytes of one launch, from its shapes: every real query of the batch
gathers at least two consecutive ``[8, 128]`` int32 tiles (8 KB; wider
brackets gather more, padded slots gather too: both left out, so the
share errs low), and the outputs are a few words per query (left out).
Real queries per launch are the batcher's specs over the family's
launches, as the program counts them over the measured window.
"""

MODULES = [r"^jit__scatter_batch$", r"^jit__scatter_many$"]
TILE_BYTES = 8 * 128 * 4
MIN_TILES = 2


def queries_per_launch(ctx, family: str) -> float:
    launches = ctx["family_launches"].get(family, 0)
    if launches <= 0:
        return 1.0
    return max(1.0, ctx["counter_delta"](["batcher.specs"]) / launches)


def least_bytes_per_launch(ctx) -> float:
    return queries_per_launch(ctx, "scatter") * MIN_TILES * TILE_BYTES
