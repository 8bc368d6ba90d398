"""Device time per decode step: the spans of the decode loops of the traced
batches (the longest ``while`` op of each ``generate`` or
``resume_from_cache`` run), over the steps those loops ran (their trip
counts, read from the decode attention kernel's events in the trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    loops = ctx.trace_mod.decode_loops(ctx.trace)
    steps = ctx.trace_mod.decode_steps(ctx.trace,
                                       ctx.config["num_hidden_layers"])
    if not loops or len(steps) != len(loops) or not sum(steps):
        return None
    return 1e3 * sum(e.dur for e in loops) / sum(steps)
