"""Tiles the flash kernels visit over the tiles of the square, by area, all
layers of the period (the program's ``attn/call`` notes)."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.tiles_visited_pct(ctx)
