"""Flash attention's share of its roofline in the query prefill behind a
compressed prefix (%), from the traced kernel time and the recorded
prefill calls' widths and bases."""

from chipbench import readers


def read(ctx):
    return readers.prefill_attn_roofline(ctx)
