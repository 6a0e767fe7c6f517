"""Device time of the prefill programs over that of the prefill and
decode programs under a backlog (%): whether batch-1 admission sets the
pace."""

from chipbench import readers


def read(ctx):
    return readers.prefill_share(ctx)
