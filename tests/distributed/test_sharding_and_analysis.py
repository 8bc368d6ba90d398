"""Sharding rules + HLO analysis.

The mesh-requiring test runs in a SUBPROCESS whose *environment* carries
--xla_force_host_platform_device_count=8, so the main pytest process keeps
a single device (per the assignment's conftest rule).  The flag must be in
the env before the subprocess imports jax — an in-process
``os.environ["XLA_FLAGS"] = ...`` mutation silently no-ops once jax has
initialised its backend, which is also why the snippet itself never touches
os.environ.  If the subprocess still comes up with fewer than 8 devices
(e.g. an env that pins XLA_FLAGS without the device-count flag), the test
skips cleanly instead of asserting on a half-built mesh."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (param_spec, params_pspecs,
                                        zero_shard_spec)
from repro.launch.analysis import (analyze_hlo_text, parse_hlo, shape_bytes,
                                   shape_elems)
from repro.models import model as M


def test_param_spec_rules():
    cfg = get_config("qwen1.5-110b")        # kv=8, model=16 -> kv replicated
    assert param_spec("trunk/#0/attn/wq/kernel", (8192, 8192), cfg, 16) == \
        P(None, "model")
    assert param_spec("trunk/#0/attn/wk/kernel", (8192, 1024), cfg, 16) == P()
    assert param_spec("trunk/#0/attn/wo/kernel", (8192, 8192), cfg, 16) == \
        P("model", None)
    assert param_spec("trunk/#0/mlp/w_gate/kernel", (8192, 49152), cfg, 16) \
        == P(None, "model")
    assert param_spec("trunk/#0/mlp/w_down/kernel", (49152, 8192), cfg, 16) \
        == P("model", None)
    assert param_spec("embed", (152064, 8192), cfg, 16) == P("model", None)
    assert param_spec("trunk/#0/norm1/scale", (8192,), cfg, 16) == P()


def test_moe_expert_parallel_vs_tensor_parallel():
    ds = get_config("deepseek-v3-671b")     # 256 experts % 16 == 0 -> EP
    assert param_spec("trunk/#0/moe/w_gate", (256, 7168, 2048), ds, 16) == \
        P("model", None, None)
    mx = get_config("mixtral-8x22b")        # 8 experts, 16-way -> TP on ff
    assert param_spec("trunk/#0/moe/w_gate", (8, 6144, 16384), mx, 16) == \
        P(None, None, "model")
    assert param_spec("trunk/#0/moe/w_down", (8, 16384, 6144), mx, 16) == \
        P(None, "model", None)


def test_params_pspecs_cover_all_leaves():
    cfg = get_config("jamba-v0.1-52b").reduced()
    struct = jax.eval_shape(lambda: M.init_lm(jax.random.PRNGKey(0), cfg))
    specs = params_pspecs(cfg, struct, model_size=2)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    p_leaves = jax.tree.leaves(struct)
    assert len(s_leaves) == len(p_leaves)
    for spec, leaf in zip(s_leaves, p_leaves):
        assert len(spec) <= len(leaf.shape)
        # every sharded dim actually divides
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax == "model":
                assert dim % 2 == 0


def test_zero_shard_spec():
    sp = zero_shard_spec(P(None, "model"), (4096, 1024), ("data",), 16)
    assert sp == P("data", "model")
    sp = zero_shard_spec(P("model", None), (1024, 4096), ("pod", "data"), 32)
    assert sp == P("model", ("pod", "data"))
    # nothing divisible -> unchanged
    sp = zero_shard_spec(P(), (7,), ("data",), 16)
    assert sp == P()


# ------------------------------------------------------------------ analysis


def test_shape_parsing():
    assert shape_bytes("f32[8,64]{1,0}") == 8 * 64 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[4], s32[2])") == 24
    assert shape_elems("pred[5,5]") == 25


SYNTH_HLO = textwrap.dedent("""\
    HloModule test

    %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]) parameter(0)
      %w = f32[8,8]{1,0} get-tuple-element(%p), index=1
      %dot.1 = f32[8,8]{1,0} dot(%w, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,8]{1,0} all-reduce(%dot.1), channel_id=1, replica_groups=[1,4]<=[4]
      %i = s32[] get-tuple-element(%p), index=0
      ROOT %tup = (s32[], f32[8,8]) tuple(%i, %ar)
    }

    %cond (p2: (s32[], f32[8,8])) -> pred[] {
      %p2 = (s32[], f32[8,8]) parameter(0)
      %i2 = s32[] get-tuple-element(%p2), index=0
      %c = s32[] constant(12)
      ROOT %lt = pred[] compare(%i2, %c), direction=LT
    }

    ENTRY %main (x: f32[8,8]) -> f32[8,8] {
      %x = f32[8,8]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %t0 = (s32[], f32[8,8]) tuple(%zero, %x)
      %wh = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
      %gte = f32[8,8]{1,0} get-tuple-element(%wh), index=1
      %ag = f32[8,16]{1,0} all-gather(%gte), channel_id=2, replica_groups=[2,2]<=[4], dimensions={1}
      ROOT %dot.2 = f32[8,8]{1,0} dot(%ag, %ag), lhs_contracting_dims={1}, rhs_contracting_dims={1}
    }
    """)


def test_analyzer_trip_count_multiplication():
    rep = analyze_hlo_text(SYNTH_HLO)
    # body dot: 2*8*8*8 = 1024 flops x 12 trips; entry dot: 2*8*8*16 = 2048
    assert rep["dot_flops_per_device"] == pytest.approx(12 * 1024 + 2048)
    # all-reduce operand 256B x 12 + all-gather operand 256B x 1
    assert rep["collective_bytes_per_device"]["all-reduce"] == \
        pytest.approx(12 * 256)
    assert rep["collective_bytes_per_device"]["all-gather"] == \
        pytest.approx(256)
    assert rep["collective_op_counts"] == {"all-reduce": 1, "all-gather": 1}


SUBPROC_SNIPPET = textwrap.dedent("""\
    import json
    import jax
    if jax.device_count() < 8:           # env did not deliver the devices
        print("SKIP: %d devices" % jax.device_count())
        raise SystemExit(0)
    from repro.configs import get_config
    from repro.launch.specs import input_specs
    from repro.launch.steps import make_train_step, make_serve_step
    from repro.launch import analysis
    from repro.optim import adamw

    from repro.distributed.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen3-0.6b").reduced(num_layers=2, d_model=128,
                                            vocab_size=256)
    cfg = cfg.replace(dtype="float32", param_dtype="float32")
    spec = input_specs(cfg, "train_4k", mesh)
    fn = make_train_step(cfg, adamw.AdamWConfig())
    with mesh:
        lowered = jax.jit(fn).lower(spec["params"], spec["opt"], *spec["args"])
        compiled = lowered.compile()
        rep = analysis.analyze_compiled(compiled, mesh.size)
    print(json.dumps({
        "flops": rep["dot_flops_per_device"],
        "coll": rep["collective_bytes_total_per_device"],
        "mem": rep["memory"]["resident_bytes"]}))
    """)


def _mesh_subprocess_env() -> dict:
    """Subprocess env with 8 virtual devices: APPEND the device-count flag
    to whatever XLA_FLAGS the CI lane already set (never clobber), and
    prepend src to PYTHONPATH instead of replacing it."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env["XLA_FLAGS"] = flags
    env.setdefault("JAX_PLATFORMS", "cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
def test_small_mesh_lower_compile():
    """A reduced config lowers + compiles on a real 8-device debug mesh and
    yields nonzero flops/collectives (subprocess to isolate device count)."""
    r = subprocess.run([sys.executable, "-c", SUBPROC_SNIPPET],
                       capture_output=True, text=True, timeout=900,
                       env=_mesh_subprocess_env(), cwd=".")
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    if last.startswith("SKIP"):
        pytest.skip(f"subprocess saw too few devices: {last}")
    out = json.loads(last)
    assert out["flops"] > 0
    assert out["coll"] > 0
    assert out["mem"] > 0


def test_mesh_larger_than_host_raises():
    """Only a trivial mesh means one device; a mesh the host cannot hold
    is an error, never a quiet single-device run."""
    from repro.distributed.mesh import MeshConfig
    assert MeshConfig().build() is None
    with pytest.raises(RuntimeError, match="needs"):
        MeshConfig(data=jax.device_count() + 1).build()
