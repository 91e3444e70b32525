"""Roofline share of ADC scoring, in %: the least time for the codes of
the selected clusters' documents, the lookup tables and one lookup-add
per code (work.py) over the device time of the whole fused
score -> fuse -> top-k program, found as the program that ran inside the
engine's `fused_score_topk` span. Timing the program, not the kernel,
keeps the share on the same work whatever implements it. The entry
names the bound (memory or compute) that sets the least time."""

import work


def read(ctx):
    ms = ctx.module_ms("fused_score_topk")
    sel = ctx.selection
    if not ms or "rows_per_query" not in sel:
        return None
    ops, nbytes = work.adc_work(sel["rows_per_query"] * ctx.batch,
                                ctx.conf["pq_nsub"], ctx.batch)
    share, bound = work.roofline_share(ops, nbytes, ms / 1e3, ctx.peaks)
    return share, {"bound": bound}
