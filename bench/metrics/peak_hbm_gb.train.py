"""Largest peak_bytes_in_use over the cell's devices after the window
(GB)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
