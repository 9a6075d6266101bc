"""Prefill step's share of its roofline, per step.  A prefill step
computes every row of its wave (``max_batch`` rows) at the call's prompt
length, so its least time is the larger of those rows' operations
(``prefill_flops``) over the bf16 peak and the step's bytes
(``prefill_bytes``: every weight once, the KV of every position written)
over the bandwidth.  The mean of that over the window's requests (every
call has one prompt length) against the mean device time of the prefill
steps the trace holds: both are means, so a trace that dropped some
steps still reads."""


def read(ctx):
    n, secs = ctx.trace.module_seconds("prefill_step")
    if n == 0 or not ctx.served or secs <= 0:
        return None
    m, block, rows = ctx.model, ctx.block, ctx.cfg.max_batch
    least = [max(rows * block.prefill_flops(m, len(s.prompt))
                 / ctx.peaks["bf16_flops_per_s"],
                 block.prefill_bytes(m, len(s.prompt), rows)
                 / ctx.peaks["hbm_bytes_per_s"]) for s in ctx.served]
    return 100.0 * (sum(least) / len(least)) / (secs / n)
