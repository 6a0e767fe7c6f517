"""Mean number of live slots per batched decode step under a backlog
(slots), from the engine's decode-step and generated-token counters."""

from chipbench import readers


def read(ctx):
    return readers.occupancy(ctx)
