"""Mean time from a request's due time to the start of its admission
(ms): release lateness plus the wait in the scheduler's queue, from the
engine's ``request_log`` stamps."""

from chipbench import spans


def read(ctx):
    return spans.mean_wait_ms(ctx.served, "admitted_s")
