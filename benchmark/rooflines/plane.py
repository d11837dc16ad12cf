"""The fused match-plus-planes program (``_selected_batch``): the scatter
match and, for each matched row, the read of its genotype-plane words.

Least bytes of one launch: one query's two ``[8, 128]`` int32 tiles and
one plane row of ``ceil(n_samples / 32)`` words. How many queries a
launch carries and how many rows each matches is not counted by the
program, so one of each is taken: the share errs low.
"""

MODULES = [r"^jit__selected_batch$"]
TILE_BYTES = 8 * 128 * 4
MIN_TILES = 2


def least_bytes_per_launch(ctx) -> float:
    words = -(-int(ctx["config"]["n_samples"]) // 32)
    return MIN_TILES * TILE_BYTES + words * 4
