"""Device-idle time on chip 0 under a ``serve.*`` host phase of the loop
(not ``serve.idle``), per decode or prefill program run (ms)."""

from chipbench import spans


def read(ctx):
    return spans.host_gap_ms(ctx.trace)
