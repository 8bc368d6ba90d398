"""Routed tokens per held expert per decode step: the program's count of
routed assignments of valid tokens that land on the experts this chip
holds, in the traced batches' decode loops (``moe_held_tokens``, returned
with ``decode_steps`` in the loop's one transfer), over the steps those
loops ran times the MoE layers times the held experts.  In an
expert-parallel deployment the exchange brings every chip's tokens to the
experts that hold them; here each held expert sees only this chip's.  A
program that does not count reads nothing."""


def read(ctx):
    c = ctx.config
    recs = ctx.traced
    if not recs or any("moe_held_tokens" not in r.times for r in recs):
        return None
    slots = sum(r.times["decode_steps"] for r in recs) * c["n_routed_experts"] \
        * (c["num_hidden_layers"] - c["first_k_dense_replace"])
    if not slots:
        return None
    return sum(r.times["moe_held_tokens"] for r in recs) / slots
