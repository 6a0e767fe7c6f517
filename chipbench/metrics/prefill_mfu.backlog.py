"""Model operations of the prefill programs over their device time under
a backlog, as a share of the chip's bf16 peak (%)."""

from chipbench import readers


def read(ctx):
    return readers.program_mfu(ctx, "prefill")
