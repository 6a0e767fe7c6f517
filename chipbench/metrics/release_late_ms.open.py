"""Mean time from a request's due time to its release into the
scheduler (ms): how late the serving loop notices a due arrival."""

from chipbench import spans


def read(ctx):
    return spans.mean_wait_ms(ctx.served, "released_s")
