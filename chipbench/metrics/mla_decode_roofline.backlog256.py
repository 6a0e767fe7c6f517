"""The latent decode kernel's share of its roofline (%): the least time
the recorded decode calls' latent reads and operations could take, over
the kernel's traced device time."""

from chipbench import flops, flops_mla_moe

KERNEL = {"mla_latent_decode": ("mla_latent_decode",)}


def read(ctx):
    t = flops_mla_moe.device_seconds(ctx.trace, KERNEL)
    calls = ctx.served.calls.get("decode", [])
    if not t or not calls:
        return None
    dims = flops_mla_moe.Dims.of(ctx.cell.config)
    ideal = 0.0
    for lengths in calls:
        w = flops_mla_moe.latent_decode(dims, lengths + 1)
        ideal += flops.roofline_seconds(w["flops"], w["bytes"], ctx.peak)
    return 100.0 * ideal / t
