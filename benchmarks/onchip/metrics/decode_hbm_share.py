"""Share of the HBM roofline that the decode loop reaches: the least time
for the bytes each decode step needs (every weight read once, plus the
live K/V of the rows still generating) at the chip's HBM bandwidth, summed
over the traced batches' steps, over the device time of their decode loops.
The FLOP bound of a decode step at 8 rows is two orders lower, so bytes
bound it."""


def read(ctx):
    if ctx.trace is None:
        return None
    loops = ctx.trace_mod.decode_loops(ctx.trace)
    if len(loops) != len(ctx.traced) or not loops:
        return None
    # a loop whose kernel events are not whole steps was not traced whole
    steps = ctx.trace_mod.decode_steps(ctx.trace,
                                       ctx.config["num_hidden_layers"])
    if len(steps) != len(loops):
        return None
    nbytes = sum(ctx.flops.decode_bytes(ctx.config, r.steps, r.decode_rows)
                 for r in ctx.traced)
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(e.dur for e in loops)
