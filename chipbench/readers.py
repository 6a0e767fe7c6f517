"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Every reader returns None where its run holds nothing to read (no traced
kernel of that name, no recorded call), never 0 for a share."""

from __future__ import annotations

from chipbench import flops


def decode_attn_roofline(ctx):
    """Paged decode attention: the least time its calls could take (each
    slot reads its whole context, prefix included, and the new token),
    over the kernel's device time, in percent."""
    t = ctx.trace.kernel_seconds("paged_decode")
    calls = ctx.served.calls.get("decode", [])
    if not t or not calls:
        return None
    ideal = 0.0
    for lengths in calls:
        w = flops.decode_step(ctx.shape, lengths + 1)
        ideal += flops.roofline_seconds(w["kernel_flops"], w["kernel_bytes"],
                                        ctx.peak)
    return 100.0 * ideal / t


def prefill_attn_roofline(ctx):
    """Flash attention of the prefill behind a prefix (the query rows
    read every prefix row and, causally, each other)."""
    t = ctx.trace.kernel_seconds("flash")
    calls = ctx.served.calls.get("prefill", [])
    if not t or not calls:
        return None
    ideal = 0.0
    for width, base in calls:
        w = flops.prefill(ctx.shape, width, base)
        ideal += flops.roofline_seconds(w["kernel_flops"], w["kernel_bytes"],
                                        ctx.peak)
    return 100.0 * ideal / t


def program_mfu(ctx, program: str):
    """Model operations of a step program's calls over the device time of
    its runs, as a share of the chip's peak."""
    t = ctx.trace.program_seconds(program)
    calls = ctx.served.calls.get(program, [])
    if not t or not calls:
        return None
    if program == "decode":
        work = sum(flops.decode_step(ctx.shape, lengths + 1)["model_flops"]
                   for lengths in calls)
    else:
        work = sum(flops.prefill(ctx.shape, w, b)["model_flops"]
                   for w, b in calls)
    return 100.0 * work / t / ctx.peak["bf16_flops_per_s"]


def prefill_share(ctx):
    """Device time of the prefill programs over that of the prefill and
    decode programs, in percent: how much of the step work is batch-1
    admission."""
    pre = ctx.trace.program_seconds("prefill")
    dec = ctx.trace.program_seconds("decode")
    if not pre or not dec:
        return None
    return 100.0 * pre / (pre + dec)


def occupancy(ctx):
    """Mean live slots per decode step: the engine counts one generated
    token per live slot in each batched decode step."""
    eng = (ctx.served.stats or {}).get("engine") or {}
    steps = eng.get("decode_steps")
    if not steps:
        return None
    return eng["tokens_generated"] / steps
