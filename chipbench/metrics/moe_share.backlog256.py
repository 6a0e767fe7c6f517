"""Device time of the MoE layers' operations (the expert kernels, and
every op that reads a router, held-expert or shared-expert weight) over
that of the decode and prefill programs (%)."""

from chipbench import flops_mla_moe


def read(ctx):
    moe = flops_mla_moe.device_seconds(
        ctx.trace, flops_mla_moe.moe_labels(ctx.cell.config))
    steps = (ctx.trace.program_seconds("decode")
             + ctx.trace.program_seconds("prefill"))
    if not moe or not steps:
        return None
    return 100.0 * moe / steps
