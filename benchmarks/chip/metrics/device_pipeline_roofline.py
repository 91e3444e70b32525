"""Roofline share of the one-jit device program, in %: the least time the
chip needs for the work the answers need (the selected clusters' document
rows read and dotted, the query terms' postings read; work.py) over the
program's device time per call. The entry names the bound (memory or
compute) that sets the least time."""

import work


def read(ctx):
    ms = ctx.module_ms("device_pipeline")
    sel = ctx.selection
    if not ms or "rows_per_query" not in sel:
        return None
    flops, nbytes = work.device_pipeline_work(
        sel["rows_per_query"] * ctx.batch,
        sel["postings_per_query"] * ctx.batch, ctx.conf["dim"])
    share, bound = work.roofline_share(flops, nbytes, ms / 1e3, ctx.peaks)
    return share, {"bound": bound}
