"""Host time per batch of the collection step: the batch's wall time minus
the rollout stages the program stamps after a block_until_ready (verify,
compact, decode, assembly).  What remains is the cache get and put, the
reward, the numpy conversions and the dispatch gaps between stages.  Read
where the program's stage stamps all block (the reuse path)."""


def read(ctx):
    keys = ("verify_time", "compact_time", "decode_time", "assembly_time")
    recs = [r for r in ctx.records if r.times.get("one_pass") == 1.0]
    if not recs:
        return None
    host = [r.wall - sum(r.times[k] for k in keys) for r in recs]
    return 1e3 * sum(host) / len(host)
