"""The grouped expert matmuls' share of their roofline (%): the least time
the window's routed rows (the engine's ``stats()["moe"]`` counters) could
take through the held experts, over the traced device time of the
``expert_gmm`` kernels and of the fetches of their weights."""

from chipbench import flops, flops_mla_moe


def read(ctx):
    c = ctx.cell.config
    t = flops_mla_moe.device_seconds(ctx.trace,
                                     flops_mla_moe.expert_matmul_labels(c))
    moe = (ctx.served.stats or {}).get("moe") or {}
    if not t or not moe.get("routed_rows"):
        return None
    w = flops_mla_moe.expert_gmm(flops_mla_moe.Dims.of(c),
                                 moe["routed_rows"], moe["expert_hits"])
    return 100.0 * flops.roofline_seconds(w["flops"], w["bytes"],
                                          ctx.peak) / t
