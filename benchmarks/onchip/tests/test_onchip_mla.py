"""The deepseek_v3_moe family in the harness: its reference against the
program at a tiny size on the CPU (a sound run is correct; the float8
control and a token altered where it is sampled are not), the operation
and byte counts of ``harness/flops_mla_moe.py`` against hand counts, and
the latent-attention / held-expert readers on synthetic contexts."""
import os
import time

import jax
import numpy as np
import pytest

import onchip_tiny as T
import onchip_tiny_mla as TM
from harness import check, faults, flops_mla_moe, peaks, trace
from harness import cell as C
from harness.cell import Context, Record, load_module

SEED = 11


@pytest.fixture(autouse=True)
def _fresh_compiles():
    jax.clear_caches()
    yield
    jax.clear_caches()


def run(seed):
    return C.run(TM.CELL, TM.ENTRY, T.REUSE, [], seed=seed, seconds=0.3,
                 trace_on=False, t_start=time.perf_counter(),
                 log=lambda s: None)


def test_sound_run_is_correct_and_counts_held_assignments():
    res = run(SEED)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] % 16 == 0
    s = C.Setup(TM.ENTRY, T.REUSE, SEED)
    r = C.collect(s.collector, s.params, s.traffic.batch(0),
                  time.perf_counter)
    assert r.times["one_pass"] == 1.0
    # every valid decoded token routes 3 of 8 experts, in 2 MoE layers
    assert 0 < r.times["moe_held_tokens"] <= 3 * 2 * r.n_generated


def test_control_fails_the_limit():
    s = C.Setup(TM.ENTRY, T.REUSE, SEED)
    recs = [C.collect(s.collector, s.params, s.traffic.batch(i),
                      time.perf_counter) for i in range(2)]
    rows = [(r, b) for r in recs for b in range(len(r.length))]
    prog, ref = C.reference_logprobs(s.ref, s.sizes, s.wkey, rows, s.traffic,
                                     "reference")
    _, ctl = C.reference_logprobs(s.ref, s.sizes, s.wkey, rows, s.traffic,
                                  "control")
    limits = C.limits_for(TM.ENTRY)
    values = {"lp_gap": check.lp_gap(prog, ref), "rows_off": 0.0,
              "reused_off": 0.0}
    assert check.verdict(values, limits)
    assert not check.verdict(dict(values, lp_gap=check.lp_gap(ctl, ref)),
                             limits)


def test_altered_token_is_not_correct():
    with faults.altered_token():
        res = run(2 ** 31 + 11)
    assert not res["correct"]
    assert res["check"]["lp_gap"]["value"] > res["check"]["lp_gap"]["limit"]


# ----------------------------------------------------------------- flops


CM = {"num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 4,
      "num_attention_heads": 2, "kv_lora_rank": 3, "qk_nope_head_dim": 2,
      "qk_rope_head_dim": 1, "v_head_dim": 2, "intermediate_size": 5,
      "moe_intermediate_size": 2, "n_router_experts": 6,
      "n_shared_experts": 2, "n_routed_experts": 2, "vocab_size": 10}


def test_moonlight_file_states_its_cut():
    """The file's top-level keys (the published config.json as run) and the
    harness's ``config`` copy agree; what differs from the source is listed
    in ``reduced`` beside the published value; the family accepts it."""
    import json
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs",
                           "moonlight-16b-a3b-ep8.json")) as f:
        entry = json.load(f)
    keys = list(entry)
    published = keys[:keys.index("source_url")]
    assert "hidden_size" in published and "vocab_size" in published
    for k in published:
        assert entry[k] == entry["config"][k], k
    assert sorted(entry["reduced"]) == sorted(entry["published"])
    for k in entry["reduced"]:
        assert entry[k] < entry["published"][k], k
    assert entry["config"]["n_router_experts"] == \
        entry["published"]["n_routed_experts"]
    ref = load_module(os.path.join(here, "configs", "deepseek_v3_moe.py"),
                      "t_moonlight_ref")
    assert ref.sizes_of(entry["config"], entry["eos_logit_margin"])


def test_parameter_counts_by_hand():
    # wq 4*2*3 + wkv_a 4*4 + wkv_b 3*2*4 + wo 2*2*4 = 24+16+24+16
    assert flops_mla_moe.attention_params(CM) == 80
    assert flops_mla_moe.expert_params(CM) == 3 * 4 * 2
    # 3 attention + 1 dense FFN 3*4*5 + 2 MoE layers (router 4*6 + two
    # shared experts 2*24) + head 10*4
    assert flops_mla_moe.dense_params(CM) == 240 + 60 + 2 * (24 + 48) + 40


def test_forward_flops_by_hand():
    # T=2, 0.5 held assignments a token and MoE layer: 2*484*2 dense
    # matmuls, 2*24*0.5*2 layers*2 tokens routed, attention
    # 3 layers * 2 heads * 2*(2+1+2) * (1+2) pairs
    assert flops_mla_moe.forward_flops(CM, 2, 0.5) == \
        2 * 484 * 2 + 2 * 24 * 0.5 * 2 * 2 + 3 * 2 * 10 * 3


def test_latent_decode_cost_by_hand():
    rows = [(5, 2), (3, 0), (1, 1)]            # 15 live tokens, 3 steps
    f, b = flops_mla_moe.mla_decode_attention_cost(CM, rows)
    assert f == 3 * 2 * 2 * (2 * 3 + 1) * 15
    # latent + rope 3*(3+1)*2 bytes a token; q + out 2*(2*3+1)*2 a step
    assert b == 24 * 15 + 3 * 3 * 2 * 7 * 2


# ----------------------------------------------------------------- readers


def _metric(name):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_module(os.path.join(here, "metrics", name + ".py"),
                       "t_" + name)


def _record(n_gen, steps, held, length=(5, 4), prompt_len=(2, 3)):
    B, P = len(length), 8
    mask = np.zeros((B, P), bool)
    for b, p in enumerate(prompt_len):
        mask[b, P - p:] = True
    z = np.zeros((B, 16))
    times = {"one_pass": 1.0, "decode_steps": steps}
    if held is not None:
        times["moe_held_tokens"] = held
    return Record(0, 0.0, 1.0, times, z.astype(np.int32), mask,
                  z.astype(np.int32), z, np.asarray(length),
                  np.zeros(B, np.int32), np.zeros(B, bool), None, None, 0,
                  n_gen)


def _kernels(name, s, e, k):
    w = (e - s) / k
    return [trace.Event(name, s + j * w, s + (j + 1) * w) for j in range(k)]


def _trace(kernel="%decode_attention_mla.24 = (f32[16,1,5,16,1]) "
                  "custom-call(...)"):
    """One resume run whose decode loop ran 4 steps of CM's 3 layers."""
    ev = trace.Event
    return trace.Trace(
        ops={0: _kernels(kernel, 1.2, 2.2, 12)
             + [ev("%fusion.3 = bf16[16,2048] fusion(...)", 2.2, 4.8)]},
        loops={0: [ev("%while.62 = (s32[]) while(...)", 1.1, 4.9)]},
        modules={0: [ev("jit_resume_from_cache(7)", 1.0, 5.0)]}, host=[])


def test_latent_decode_readers_on_a_synthetic_trace():
    r = _record(6, 4, None, length=(4, 3), prompt_len=(2, 3))
    pk = peaks.peaks_for("TPU v5 lite")
    ctx = Context(CM, [], 1.0, traced=[r], trace=_trace(), trace_lo=0.0,
                  trace_hi=10.0, peaks=pk)
    # the engine's own reader counts the latent kernel's events as steps
    assert _metric("decode_ms_per_step").read(ctx) == \
        pytest.approx(1e3 * 3.8 / 4)
    f, b = flops_mla_moe.mla_decode_attention_cost(CM, r.decode_rows)
    assert _metric("mla_decode_attention_roofline").read(ctx) == \
        pytest.approx(100 * max(f / 197e12, b / 819e9) / 1.0)
    # a program whose decode loop runs another kernel reads nothing
    gqa = Context(CM, [], 1.0, traced=[r], trace=_trace(
        "%decode_attention.5 = (f32[8]) custom-call(...)"), trace_lo=0.0,
        trace_hi=10.0, peaks=pk)
    assert _metric("mla_decode_attention_roofline").read(gqa) is None
    assert _metric("mla_decode_attention_roofline").read(
        Context(CM, [], 1.0)) is None


def test_held_expert_readers():
    # two traced batches: 10 + 6 held assignments over (4 + 2) steps of
    # 2 MoE layers x 2 held experts
    a, b = _record(6, 4, 10.0), _record(3, 2, 6.0)
    pk = peaks.peaks_for("TPU v5 lite")
    ctx = Context(CM, [a, b], 2.0, traced=[a, b], peaks=pk)
    assert _metric("moe_tokens_per_held_expert").read(ctx) == \
        pytest.approx(16 / (6 * 2 * 2))
    # routed work at the measured rate: 16 held over 9 tokens x 2 layers
    rate = 16 / (9 * 2)
    work = sum(flops_mla_moe.forward_flops(CM, p + L, rate)
               for p, L in ((2, 5), (3, 4))) * 2
    assert _metric("mla_moe_mfu").read(ctx) == \
        pytest.approx(100 * work / (2.0 * 197e12))
    # a program that does not count held assignments reads nothing
    bare = _record(6, 4, None)
    ctx = Context(CM, [bare], 1.0, traced=[bare], peaks=pk)
    for name in ("moe_tokens_per_held_expert", "mla_moe_mfu"):
        assert _metric(name).read(ctx) is None
