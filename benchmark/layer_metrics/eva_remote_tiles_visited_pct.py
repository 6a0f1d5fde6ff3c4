"""Tiles the remote calls' kernels visit over the tiles of their whole
``[T, T / chunk]`` rectangles, forward and backward (the program's
``attn/call`` notes of kind ``stair``): 37.5 where only the visible
window-by-window blocks are visited (6 of 16 at four windows)."""

from benchmark import eva_reduce


def read(ctx):
    return eva_reduce.remote_tiles_visited_pct(ctx)
