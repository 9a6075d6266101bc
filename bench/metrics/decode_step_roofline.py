"""Decode step's share of its roofline: the least time of the window's
decode steps (``bench.flops.decode_least_seconds`` over the block's
counts: weights at their served dtype read once per step, the logical
bfloat16 KV of the tokens each active row attends at its own prompt
length, one token written per row) over their measured device time.
Nothing when the trace does not hold every decode step of the window."""

from bench import flops


def read(ctx):
    n, secs = ctx.trace.module_seconds("decode_step")
    if n == 0 or n != ctx.decode_steps or secs <= 0:
        return None
    least = flops.decode_least_seconds(
        ctx.block, ctx.model, n, ctx.decode_ctxs(),
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
