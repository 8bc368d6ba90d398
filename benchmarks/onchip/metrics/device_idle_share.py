"""Share of the traced batches' span in which no operation runs on the
device: 1 - (union of the device's op intervals) / (traced span)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops or ctx.trace_hi <= ctx.trace_lo:
        return None
    span = ctx.trace_hi - ctx.trace_lo
    busy = [ctx.trace_mod.busy_s(ev, ctx.trace_lo, ctx.trace_hi)
            for ev in tr.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
