"""Verify forward time per batch: the program's ``verify_time`` stamp
(prefilling verification over prompt + draft, ending at a
block_until_ready), summed over the window's batches, over their count."""


def read(ctx):
    recs = [r for r in ctx.records if r.times.get("one_pass") == 1.0]
    if not recs:
        return None
    return 1e3 * sum(r.times["verify_time"] for r in recs) / len(recs)
