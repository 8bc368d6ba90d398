"""Runtime mesh execution for the SPEC-RL loop (DESIGN.md §8).

``launch/`` owns the *static* side of distribution — partition rules,
ShapeDtypeStruct dry-runs, HLO analysis.  This module owns the *runtime*
side: a ``MeshConfig`` the launchers plumb into the trainer / rollout /
serving stack, plus the helpers that place live arrays on the mesh:

* params / optimizer moments via the ``param_spec`` rules,
* batch rows over the ``data`` axis,
* decode caches batch-over-``data`` and KV-heads-over-``model``.

A trivial (1×1) ``MeshConfig`` builds no mesh (``None``), and every helper
accepts ``mesh=None`` as a no-op: that is the single-device path.  A mesh
larger than the host raises.  Meshes may also lack an axis entirely (the
per-data-shard serving submeshes carry only ``model``), so all axis lookups
are presence-checked.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

from .sharding import params_pspecs


def make_mesh(shape, names, devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings
    through plain jnp code (``jax.make_mesh`` defaults to ``Explicit``
    axes, under which an unannotated gather such as the embedding lookup
    is a type error)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


@dataclass(frozen=True)
class MeshConfig:
    """Axis sizes for the runtime (data, model) mesh.

    ``build()`` materialises the mesh over the first ``data * model`` host
    devices, and raises when the host has fewer.  A trivial (1, 1) config
    yields ``None``, which every consumer treats as "run exactly the
    unsharded path".
    """
    data: int = 1
    model: int = 1

    @property
    def size(self) -> int:
        return self.data * self.model

    def build(self) -> Optional[Mesh]:
        if self.size <= 1:
            return None
        if jax.device_count() < self.size:
            raise RuntimeError(
                f"MeshConfig({self.data}x{self.model}) needs {self.size} "
                f"devices, found {jax.device_count()} (set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N for "
                "virtual CPU devices)")
        return make_mesh((self.data, self.model), ("data", "model"))


# ------------------------------------------------------------------ axis info


def data_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    out = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            out *= mesh.shape[a]
    return out


def model_size(mesh: Optional[Mesh]) -> int:
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def _data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_submeshes(mesh: Mesh):
    """One model-only submesh per ``data``-shard row of the device grid.

    The per-shard serving schedulers (DESIGN.md §8) each run on one of
    these: disjoint devices, ``model`` axis only.  A mesh without a data
    axis is its own (single) submesh.
    """
    import numpy as np
    if "data" not in mesh.axis_names or mesh.shape["data"] <= 1:
        return [mesh]
    axis = mesh.axis_names.index("data")
    devs = np.moveaxis(np.asarray(mesh.devices), axis, 0)
    names = tuple(a for a in mesh.axis_names if a != "data")
    if not names:
        devs = devs.reshape(devs.shape[0], 1)
        names = ("model",)
    return [Mesh(d, names) for d in devs]


def batch_pspec(mesh: Mesh, ndim: int, batch: int) -> P:
    """Leading-dim partition over the data axes; replicate when indivisible."""
    axes = _data_axes(mesh)
    dsz = data_size(mesh)
    if not axes or dsz <= 1 or batch % dsz != 0 or batch < dsz:
        return P(*([None] * ndim))
    first = axes if len(axes) > 1 else axes[0]
    return P(first, *([None] * (ndim - 1)))


# ------------------------------------------------------------------ placement


def replicate(mesh: Optional[Mesh], tree):
    if mesh is None:
        return tree
    return jax.device_put(tree, NamedSharding(mesh, P()))


def host_fetch(tree):
    """Gather a (possibly sharded) pytree to host numpy arrays.

    The §12 weight-publication path uses this when the sync channel must
    carry a self-contained copy across failure domains (a transport that
    serialises, or a producer on another host) — by default WeightSync
    hands the live device arrays through untouched, which keeps the K=0
    identity contract and the sharding layout intact."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def shard_batch(mesh: Optional[Mesh], tree):
    """device_put every leaf with its leading dim over the data axes."""
    if mesh is None:
        return tree
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, batch_pspec(mesh, jnp.ndim(x), jnp.shape(x)[0]
                              if jnp.ndim(x) else 1))), tree)


def param_shardings(mesh: Mesh, cfg: ModelConfig, params):
    pspecs = params_pspecs(cfg, params, model_size(mesh))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(mesh: Optional[Mesh], cfg: ModelConfig, params):
    """Place a params pytree per the ``param_spec`` partition rules."""
    if mesh is None:
        return params
    return jax.device_put(params, param_shardings(mesh, cfg, params))


def shard_opt_state(mesh: Optional[Mesh], cfg: ModelConfig, params, opt_state):
    """AdamW moments follow the param layout; ``step`` is replicated."""
    if mesh is None:
        return opt_state
    sh = param_shardings(mesh, cfg, params)
    return {"mu": jax.device_put(opt_state["mu"], sh),
            "nu": jax.device_put(opt_state["nu"], sh),
            "step": jax.device_put(opt_state["step"], NamedSharding(mesh, P()))}


# ------------------------------------------------------------------ KV caches


def _cache_leaf_pspec(shape, cfg: ModelConfig, mesh: Mesh,
                      kv_heads: bool) -> P:
    """Partition for one trunk-cache leaf (leading axis = scan run)."""
    b_ax = None
    if len(shape) >= 2:
        dsz = data_size(mesh)
        axes = _data_axes(mesh)
        if axes and dsz > 1 and shape[1] % dsz == 0 and shape[1] >= dsz:
            b_ax = axes if len(axes) > 1 else axes[0]
    spec = [None, b_ax] + [None] * (len(shape) - 2)
    if kv_heads:
        msz = model_size(mesh)
        if msz > 1 and shape[2] % msz == 0 and shape[2] >= msz:
            spec[2] = "model"
    return P(*spec)


def decode_cache_pspecs(cfg: ModelConfig, caches, mesh: Mesh, *,
                        batch: bool = True):
    """Same-structure pytree of PartitionSpecs for a trunk decode cache.

    Batch (axis 1, after the scan-run axis) shards over ``data``; the KV head
    axis of attention ``k``/``v`` buffers shards over ``model`` when the head
    count divides (uneven heads — MQA/GQA with few KV heads — replicate,
    mirroring ``param_spec``'s kv rule).  MLA latents (``ckv``/``krope``)
    and recurrent state shard on batch only.  ``batch=False`` suppresses the
    data-axis entry — the serving slot engine keeps its persistent decode
    batch whole per data shard (one scheduler per shard, DESIGN.md §8) and
    shards only the KV head axis.
    """
    out = []
    for run in caches:
        new_run = {}
        for group, sub in run.items():
            paged = "table" in sub
            new_sub = {}
            for name, leaf in sub.items():
                kv_heads = group == "self" and name in ("k", "v") \
                    and leaf.ndim == 5
                if paged:
                    # §13 paged layout: pool axis 1 is the GLOBAL block
                    # pool — rows of DIFFERENT slots interleave there, so
                    # it must never shard like a batch axis.  Replicate
                    # everything except the GQA pool head axis (axis 2,
                    # same slot as dense), which shards over ``model``.
                    spec = [None] * leaf.ndim
                    if kv_heads:
                        msz = model_size(mesh)
                        if msz > 1 and leaf.shape[2] % msz == 0:
                            spec[2] = "model"
                    new_sub[name] = P(*spec)
                    continue
                spec = _cache_leaf_pspec(leaf.shape, cfg, mesh, kv_heads)
                if not batch and len(spec) > 1:
                    spec = P(spec[0], None, *spec[2:])
                new_sub[name] = spec
            new_run[group] = new_sub
        out.append(new_run)
    return out


def cache_shardings(cfg: ModelConfig, caches, mesh: Mesh, *,
                    batch: bool = True):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        decode_cache_pspecs(cfg, caches, mesh, batch=batch),
                        is_leaf=lambda x: isinstance(x, P))


def constrain_caches(cfg: ModelConfig, caches, mesh: Optional[Mesh], *,
                     batch: bool = True):
    """``with_sharding_constraint`` every cache leaf (jit-traceable)."""
    if mesh is None:
        return caches
    return jax.tree.map(jax.lax.with_sharding_constraint, caches,
                        cache_shardings(cfg, caches, mesh, batch=batch))


def shard_caches(cfg: ModelConfig, caches, mesh: Optional[Mesh], *,
                 batch: bool = True):
    """Eager placement of a live cache pytree (serving persistent caches)."""
    if mesh is None:
        return caches
    return jax.device_put(caches, cache_shardings(cfg, caches, mesh,
                                                  batch=batch))
