"""Paged decode attention's share of its roofline (%), from the traced
kernel time and the recorded decode calls' per-slot lengths."""

from chipbench import readers


def read(ctx):
    return readers.decode_attn_roofline(ctx)
