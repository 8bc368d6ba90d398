"""Shared fixtures.  NOTE: no XLA_FLAGS device-count override here — smoke
tests and benches must see the single real CPU device (the 512-device
override belongs ONLY to repro.launch.dryrun)."""
import contextlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Make tests/hypothesis_compat.py importable from every test subdirectory
# (the test tree has no __init__.py files, so pytest only puts each test
# module's own directory on sys.path).
sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig


@pytest.fixture(scope="module", autouse=True)
def _bound_compile_maps():
    """Release compiled executables after every test module.

    Each XLA:CPU executable keeps mmap'd JIT code regions alive for the
    life of the process; a full-suite run accumulates enough of them to
    cross the kernel's ``vm.max_map_count`` ceiling (default 65530), at
    which point the NEXT compile segfaults inside LLVM.  Cross-module
    jit reuse is negligible (modules build their own configs/shapes), so
    clearing per module bounds the map count at a few thousand for the
    whole suite."""
    yield
    jax.clear_caches()


@pytest.fixture
def interpret_kernels(monkeypatch):
    """``with interpret_kernels("cache_gather", ...):`` runs the named kernel
    packages in Pallas interpret mode wherever the program leaves them at
    ``"auto"`` (off a TPU), by patching ``repro.kernels.resolve``.  The
    choice is made at trace time, so compiled programs are dropped on entry
    and on exit."""
    import repro.kernels as K

    @contextlib.contextmanager
    def use(*names):
        resolve = K.resolve

        def interpret(kernel, impl, ref):
            if (impl == "auto" and kernel in names
                    and jax.default_backend() != "tpu"):
                return "interpret"
            return resolve(kernel, impl, ref)
        jax.clear_caches()
        try:
            with monkeypatch.context() as m:
                m.setattr(K, "resolve", interpret)
                yield
        finally:
            jax.clear_caches()
    return use


@pytest.fixture(scope="session")
def tiny_cfg():
    from repro.data.tokenizer import VOCAB_SIZE
    return ModelConfig(name="tiny", num_layers=2, d_model=64, num_heads=4,
                       num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                       max_seq_len=256)


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg):
    from repro.models import model as M
    return M.init_lm(jax.random.PRNGKey(0), tiny_cfg)
