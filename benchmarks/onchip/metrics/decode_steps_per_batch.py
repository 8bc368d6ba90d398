"""Decode-loop iterations per batch: the mean over the window's batches of
the program's ``decode_steps`` count, the decode ``while_loop``'s trip
count as the loop itself returns it (engine/generate.py).  A program that
does not count them reads nothing."""


def read(ctx):
    steps = [r.times.get("decode_steps") for r in ctx.records]
    if not steps or None in steps:
        return None
    return sum(steps) / len(steps)
