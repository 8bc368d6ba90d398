"""The collection step's own spans: one ``Collector.collect`` on the tiny
model gives the span tree of the fresh path, then of the reuse path, each
span under its parent and all of one call sharing its ``batch`` id, and
the same tree lands in a profiler trace's host plane."""
import jax
import pytest

from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.obs import Tracer, configure, reset
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import Collector, RLConfig

N = 8
ROLLOUT = "rollout.rollout"
FRESH_TREE = {
    ("trainer.collect", None), (ROLLOUT, "trainer.collect"),
    ("trainer.reward", "trainer.collect"),
    ("rollout.cache_get", ROLLOUT), ("rollout.generate", ROLLOUT),
    ("rollout.cache_put", ROLLOUT), ("rollout.to_host", ROLLOUT)}
REUSE_TREE = (FRESH_TREE - {("rollout.generate", ROLLOUT)}) | {
    ("rollout.verify", ROLLOUT), ("rollout.compact", ROLLOUT),
    ("rollout.decode", ROLLOUT), ("rollout.assembly", ROLLOUT)}


def _name(sp):
    return f"{sp.track}.{sp.name}"


@pytest.fixture()
def collected(tiny_cfg, tiny_params, tmp_path):
    """Two collects of one batch (epoch 0 fresh, epoch 1 reusing epoch
    0's trajectories) under an enabled tracer and a profiler trace."""
    problems = generate_problems(MathTaskConfig(num_problems=4,
                                                max_operand=4))
    rl = RLConfig(algo="grpo", group_size=2, prompts_per_batch=2,
                  max_new_tokens=N)
    tr = Tracer()
    configure(tracer=tr)
    try:
        col = Collector(tiny_cfg, rl, SpecConfig(verify_impl="ref"),
                        PromptDataset(problems, max_prompt_len=10),
                        jax.random.PRNGKey(0))
        batch = col.sample(0)
        jax.profiler.start_trace(str(tmp_path))
        out = [col.collect(tiny_params, batch, e) for e in (0, 1)]
        jax.profiler.stop_trace()
    finally:
        reset()
    return tr, out, tmp_path


def test_collect_gives_the_span_tree_on_both_paths(collected):
    tr, out, _ = collected
    by_handle = {sp.handle: sp for sp in tr.spans}
    batches = sorted({sp.args["batch"] for sp in tr.spans})
    assert len(batches) == 2 and len(tr.spans) == len(by_handle)
    for bid, want, (_, rb, _, times) in zip(batches,
                                            (FRESH_TREE, REUSE_TREE), out):
        spans = [sp for sp in tr.spans if sp.args["batch"] == bid]
        got = {(_name(sp), None if sp.parent is None
                else _name(by_handle[sp.parent])) for sp in spans}
        assert got == want
        assert len(spans) == len(want)
        # rollout_time is the rollout.rollout span's duration
        (ro,) = [sp for sp in spans if _name(sp) == ROLLOUT]
        assert times["rollout_time"] == pytest.approx(ro.dur, abs=2e-3)
        assert 0 <= times["decode_steps"] <= N
    assert out[0][1].metrics["one_pass"] == 0.0
    assert out[1][1].metrics["one_pass"] == 1.0
    # the fresh path's loop runs until its longest row is done
    assert out[0][1].metrics["decode_steps"] == int(out[0][1].length.max())


def test_collect_spans_reach_the_profiler(collected):
    from jax.profiler import ProfileData
    _, _, logdir = collected
    (path,) = logdir.glob("**/*.xplane.pb")
    names = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [e.name for e in line.events]
    want = {n for n, _ in FRESH_TREE | REUSE_TREE}
    counts = {n: names.count(n) for n in want}
    assert counts["trainer.collect"] == 2
    assert counts["rollout.verify"] == 1 and counts["rollout.generate"] == 1
    assert all(c >= 1 for c in counts.values()), counts
