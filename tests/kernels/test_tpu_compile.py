"""Compile the Pallas kernels of the main path for a described TPU v5e at
real widths (qwen3-0.6b: GQA 16/8, head dim 128; a 4096-slot cache; 16
rows).  Nothing runs: the chip's compiler is asked whether it accepts each
kernel, which interpret mode cannot tell (tile-aligned blocks, lowerable
ops, VMEM).  The decode loop is compiled whole too, to check that a step
moves no whole K/V cache.  Each test skips where no v5e topology can be
described."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.cache_gather.kernel import (cache_roll_pallas,
                                               paged_gather_pallas)
from repro.kernels.cache_slot_write.kernel import cache_slot_write_pallas
from repro.kernels.decode_attention.kernel import (
    decode_attention_mla_pallas, decode_attention_pallas,
    paged_decode_attention_pallas)
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rwkv6_wkv.kernel import wkv_pallas
from repro.kernels.spec_verify.kernel import spec_verify_pallas

CFG = get_config("qwen3-0.6b")
B, HQ, HKV, D = 16, CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
S, BS = 4096, CFG.kv_block_size
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    """Compile for the described chip; the result must hold a Mosaic call."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("T", [1, 5])
def test_decode_attention(spec, T):
    """Dense decode: T=1 plain decode, T=5 a draft block of k+1 = 5."""
    _compile(decode_attention_pallas,
             spec((B, HQ, T, D), BF), spec((B, HKV, S, D), BF),
             spec((B, HKV, S, D), BF), spec((B,), I32), spec((B,), I32),
             spec((B, S), I32), spec((B,), I32), spec((B,), I32))


@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("Sc", [1280, 768])
def test_decode_attention_stacked(spec, T, Sc):
    """The whole stacked cache of qwen3-0.6b (28 layers, 8 rows, the reuse
    cell's 1280 slots and the first-epoch cells' 768, both read in
    256-slot tiles over all 8 KV heads), read at a layer given as a
    scalar."""
    stack = (CFG.num_layers, 8, HKV, Sc, D)
    _compile(decode_attention_pallas,
             spec((8, HQ, T, D), BF), spec(stack, BF), spec(stack, BF),
             spec((8,), I32), spec((8,), I32), spec((8, Sc), I32),
             spec((8,), I32), spec((8,), I32), spec((), I32))


def test_paged_decode_attention_stacked(spec):
    nb = S // BS
    NB = B * nb + 1
    pools = (CFG.num_layers, NB, HKV, BS, D)
    _compile(paged_decode_attention_pallas,
             spec((B, HQ, 1, D), BF), spec(pools, BF), spec(pools, BF),
             spec((B, nb), I32), spec((B,), I32), spec((B,), I32),
             spec((B, S), I32), spec((B,), I32), spec((B,), I32),
             spec((), I32))


# an HLO instruction line: (name, result shape without layout, opcode)
INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ([a-z0-9]+\[[\d,]*\])\S* "
                   r"([\w\-]+)\(")


def _loop_computations(hlo: str):
    """{computation: instruction lines} of every computation a ``while``
    body reaches (the loop bodies and what they call)."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line)
    called = re.compile(r"(?:calls|body|condition|to_apply|"
                        r"branch_computations)=\{?%?([\w.\-]+)")
    todo = [b for lines in comps.values() for ln in lines
            for b in re.findall(r"body=%?([\w.\-]+)", ln)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [d for ln in comps[c] for d in called.findall(ln)]
    return {c: comps[c] for c in seen}


def test_resume_decode_loop_moves_no_whole_cache(topo):
    """``resume_from_cache`` at qwen3-0.6b (B=8, a 1280-slot cache written
    from slot 768, the pallas decode kernel): inside the decode loop one
    dense ``decode_attention`` kernel call a layer run (256-slot tiles),
    no copy of the whole stacked cache bf16[28,8,8,1280,128] and no
    dynamic-slice of a whole layer bf16[8,8,1280,128]; temporaries below
    2 GB (3.76 GB while each step copied the stack)."""
    from repro.engine.generate import GenerateConfig, resume_from_cache
    from repro.models import model as M
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = CFG.replace(decode_impl="pallas", tie_embeddings=True)
    as_spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(as_spec, jax.eval_shape(
        lambda k: M.init_lm(k, cfg), jax.random.PRNGKey(0)))
    caches = jax.tree.map(as_spec, jax.eval_shape(
        lambda: M.init_cache(cfg, 8, 1280)))
    vec = lambda dt: jax.ShapeDtypeStruct((8,), dt, sharding=one_chip)
    M.reset_op_counts()
    compiled = resume_from_cache.lower(
        params, cfg, GenerateConfig(max_new_tokens=512), caches,
        jax.ShapeDtypeStruct((8, cfg.vocab_size), F32, sharding=one_chip),
        vec(I32), 768, jax.ShapeDtypeStruct((2,), jnp.uint32,
                                            sharding=one_chip),
        vec(jnp.bool_), vec(I32)).compile()
    assert M.OP_COUNTS["decode_attn_tile_256"] == \
        M.OP_COUNTS["decode_attn_inplace"] > 0
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    lines = [ln for c in _loop_computations(hlo).values() for ln in c]
    # the benchmark's trace reader counts decode steps as these calls over
    # the layer count: one per layer run, named decode_attention, not _mla
    kernels = [ln for ln in lines
               if re.match(r"\s*(?:ROOT )?%decode_attention", ln)]
    assert len(kernels) == 1 and "custom-call(" in kernels[0]
    assert not re.match(r"\s*(?:ROOT )?%decode_attention_mla", kernels[0])
    loop = [m.groups() for ln in lines for m in [INSTR.match(ln)] if m]
    assert loop
    stack = f"bf16[{cfg.num_layers},8,{HKV},1280,{D}]"
    layer = (f"bf16[8,{HKV},1280,{D}]", f"bf16[1,8,{HKV},1280,{D}]")
    copies = [i for i in loop if i[1] == stack and i[2] == "copy"]
    assert not copies, copies
    slices = [i for i in loop if i[1] in layer and "dynamic-slice" in
              i[0] + i[2]]
    assert not slices, slices
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


@pytest.mark.parametrize("T", [1, 5])
def test_decode_attention_mla_stacked(spec, T):
    """The latent kernel at Moonlight-16B-A3B's widths (16 heads, latent
    512, rope 64) over the benchmark cell's stacked latent cache (26 MoE
    layers, 16 rows, 1280 slots), read at a layer given as a scalar."""
    L, Bm, Sm = 26, 16, 1280
    _compile(lambda *a: decode_attention_mla_pallas(*a, scale=192 ** -0.5),
             spec((Bm, 16, T, 512), F32), spec((Bm, 16, T, 64), BF),
             spec((L, Bm, Sm, 512), BF), spec((L, Bm, Sm, 64), BF),
             spec((Bm,), I32), spec((Bm,), I32), spec((Bm, Sm), I32),
             spec((Bm,), I32), spec((Bm,), I32), spec((), I32))


def test_moonlight_resume_reads_the_latent_cache_in_place(topo):
    """``resume_from_cache`` at the Moonlight reuse traffic's shapes
    (Moonlight-16B-A3B, 8 of 64 experts and a 20480-word vocabulary
    held, B=16, a 1280-slot latent cache written from slot 768): the decode
    loop holds one latent kernel call per layer run and no whole-layer
    latent slice or copy of a latent stack."""
    from repro.engine.generate import GenerateConfig, resume_from_cache
    from repro.models import model as M
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = get_config("moonlight-16b-a3b").replace(
        vocab_size=20480, num_held_experts=8, decode_impl="pallas")
    Bm, Sm = 16, 1280
    as_spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(as_spec, jax.eval_shape(
        lambda k: M.init_lm(k, cfg), jax.random.PRNGKey(0)))
    caches = jax.tree.map(as_spec, jax.eval_shape(
        lambda: M.init_cache(cfg, Bm, Sm)))
    vec = lambda dt: jax.ShapeDtypeStruct((Bm,), dt, sharding=one_chip)
    compiled = resume_from_cache.lower(
        params, cfg, GenerateConfig(max_new_tokens=512), caches,
        jax.ShapeDtypeStruct((Bm, cfg.vocab_size), F32, sharding=one_chip),
        vec(I32), 768, jax.ShapeDtypeStruct((2,), jnp.uint32,
                                            sharding=one_chip),
        vec(jnp.bool_), vec(I32)).compile()
    lines = [ln for c in _loop_computations(compiled.as_text()).values()
             for ln in c]
    # the dense layer's call (its run of one is inlined) and the MoE run's
    kernels = [ln for ln in lines
               if re.match(r"\s*(?:ROOT )?%decode_attention", ln)]
    assert len(kernels) == 2 and all("custom-call(" in ln for ln in kernels)
    loop = [m.groups() for ln in lines for m in [INSTR.match(ln)] if m]
    for n, r in ((1, 512), (26, 512), (1, 64), (26, 64)):
        stack = f"bf16[{n},{Bm},{Sm},{r}]"
        assert not [i for i in loop if i[1] == stack and i[2] == "copy"]
        layer = (f"bf16[{Bm},{Sm},{r}]", f"bf16[1,{Bm},{Sm},{r}]")
        assert not [i for i in loop if i[1] in layer and "dynamic-slice"
                    in i[0] + i[2]]
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_paged_decode_attention(spec):
    nb = S // BS
    NB = B * nb + 1
    _compile(paged_decode_attention_pallas,
             spec((B, HQ, 1, D), BF), spec((NB, HKV, BS, D), BF),
             spec((NB, HKV, BS, D), BF), spec((B, nb), I32), spec((B,), I32),
             spec((B,), I32), spec((B, S), I32), spec((B,), I32),
             spec((B,), I32))


def test_spec_verify(spec):
    _compile(lambda a, b, u, n: spec_verify_pallas(a, b, u, n, 0.0),
             spec((B, 512), F32), spec((B, 512), F32), spec((B, 512), F32),
             spec((B,), I32))


@pytest.mark.parametrize("seq", [S, 202])
def test_cache_roll(spec, seq):
    """One roll program per (row, KV head); 202 is a trainer cache width
    (prompt plus budget) that is not a whole number of sublane tiles."""
    R = B * HKV
    _compile(cache_roll_pallas, spec((R, seq, D), BF), spec((R,), I32))


def test_paged_gather(spec):
    nb = S // BS
    _compile(paged_gather_pallas, spec((B * nb + 1, HKV * BS, D), BF),
             spec((B, nb), I32))


def test_cache_slot_write(spec):
    R = B * HKV
    _compile(cache_slot_write_pallas, spec((R, S, D), BF),
             spec((R // 2, S, D), BF), spec((R,), I32))


def test_flash_attention(spec):
    T = 1024
    _compile(flash_attention_pallas,
             spec((2, HQ, T, D), BF), spec((2, HKV, T, D), BF),
             spec((2, HKV, T, D), BF), spec((2, T), I32), spec((2, T), I32))


def test_rwkv6_wkv(spec):
    """rwkv6-3b heads: 40 of size 64, two sequences of 512 steps."""
    H, hd, T = 40, 64, 512
    _compile(wkv_pallas, *[spec((2 * H, T, hd), F32)] * 4,
             spec((H, hd), F32), spec((2 * H, hd, hd), F32))
