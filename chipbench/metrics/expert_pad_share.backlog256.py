"""Rows the grouped expert matmuls computed beyond those routed to a held
expert, as a share of all their rows (%), from the engine's
``stats()["moe"]`` counters over the window."""


def read(ctx):
    moe = (ctx.served.stats or {}).get("moe") or {}
    rows = moe.get("routed_rows", 0) + moe.get("padded_rows", 0)
    if not rows:
        return None
    return 100.0 * moe["padded_rows"] / rows
