"""Mesh-parallel slot serving: one scheduler per data shard (DESIGN.md §8).

The slot engine's admission scatter indexes *global* decode-batch rows, so
sharding one engine's batch over the ``data`` axis would turn every
admission into a cross-shard write.  Instead the data axis is handled one
level up: ``MeshSlotServer`` splits the (data, model) mesh into one
model-only submesh per data shard (disjoint devices), runs a full
``SlotEngine`` — scheduler, free-list, persistent caches — on each, and
round-robins incoming requests across them.  Admission is therefore
**shard-local**: a freed slot on shard i is refilled from shard i's queue
with no cross-shard traffic, and each shard's params/caches spread only
over its own ``model`` axis.

Because every request owns its PRNG streams (serving/request.py), output is
independent of which shard a request lands on — the server is
token-identical to a single engine over the same requests, which is the
§6 equivalence contract lifted to the mesh (asserted in
tests/distributed/test_mesh_rollout.py).

``stats()`` returns the gathered metrics view, produced by a type-driven
``MetricsRegistry.merge`` over the shard registries (DESIGN.md §11):
counters sum, peak gauges max, histograms merge bucket-wise, ratios
re-derive from the summed parts — plus a ``per_shard`` breakdown.  The
merge runs over the union of metric names, so a counter added to the
engine can never silently vanish from the gathered view (the pre-§11
hand-listed summation could drop fields).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from repro.distributed.mesh import data_submeshes, shard_params
from repro.engine.generate import GenerateConfig
from repro.obs import MetricsRegistry
from repro.models.config import ModelConfig

from .engine_loop import SlotEngine
from .request import Request, Response


def make_slot_engine(params, cfg: ModelConfig, gen: GenerateConfig, *,
                     mesh=None, num_slots: int, prompt_width: int,
                     spec_prefix: bool = False, log_lenience: float = 0.0,
                     chunk_steps: int = 8, draft=None, faults=None,
                     deadline_steps=None, max_queue=None,
                     overflow: str = "reject", tracer=None, ledger=None,
                     kv_pool_blocks: Optional[int] = None):
    """One factory for both mesh regimes (the single dispatch point shared
    by serving/rl_adapter.py and launch/serve.py).

    A mesh with a data axis yields a ``MeshSlotServer`` — ``num_slots`` is
    rounded down to a multiple of the shard count (floored at one slot per
    shard) and params are placed per submesh inside.  Otherwise one
    ``SlotEngine`` (head-sharding its caches when a model-only mesh is
    given); that path expects params already placed by the caller.

    §10 hardening knobs pass straight through: ``deadline_steps`` /
    ``max_queue`` / ``overflow`` apply per engine (per shard on a mesh —
    the bound is shard-local, like admission), ``faults`` is a FaultPlan
    (given to shard 0 on a mesh) or a per-shard sequence of plans.

    ``cfg.cache_layout='paged'`` selects the ``PagedSlotEngine`` (block
    pool + CoW GRPO sharing, DESIGN.md §13); ``kv_pool_blocks`` optionally
    shrinks its pool below the never-runs-dry default (per shard on a
    mesh — each shard engine owns its own allocator).
    """
    from repro.distributed.mesh import data_size
    kw = dict(num_slots=num_slots, prompt_width=prompt_width,
              spec_prefix=spec_prefix, log_lenience=log_lenience,
              chunk_steps=chunk_steps, draft=draft, faults=faults,
              deadline_steps=deadline_steps, max_queue=max_queue,
              overflow=overflow, tracer=tracer, ledger=ledger)
    if cfg.cache_layout == "paged":
        kw["kv_pool_blocks"] = kv_pool_blocks
    if mesh is not None and data_size(mesh) > 1:
        D = data_size(mesh)
        kw["num_slots"] = max(D, num_slots - num_slots % D)
        return MeshSlotServer(params, cfg, gen, mesh=mesh, **kw)
    if cfg.cache_layout == "paged":
        from .paged_engine import PagedSlotEngine
        return PagedSlotEngine(params, cfg, gen, mesh=mesh, **kw)
    return SlotEngine(params, cfg, gen, mesh=mesh, **kw)


class MeshSlotServer:
    """Per-data-shard slot engines behind one submit/run/stats frontend.

    params are placed per submesh (replicated over data, ``param_spec``-
    sharded over model); ``num_slots`` is the TOTAL slot count, split evenly
    across shards (it must divide).  The frontend mirrors ``SlotEngine``:
    ``submit`` / ``run(arrivals=...)`` / ``responses`` / ``stats``.
    """

    def __init__(self, params, cfg: ModelConfig, gen: GenerateConfig, *,
                 mesh, num_slots: int, prompt_width: int,
                 spec_prefix: bool = False, log_lenience: float = 0.0,
                 chunk_steps: int = 8, draft=None, faults=None,
                 deadline_steps=None, max_queue=None,
                 overflow: str = "reject", tracer=None,
                 ledger=None, kv_pool_blocks: Optional[int] = None):
        self.submeshes = data_submeshes(mesh)
        D = len(self.submeshes)
        assert num_slots % D == 0 and num_slots >= D, \
            (f"num_slots={num_slots} must split evenly over {D} data shards")
        self.cfg, self.gen = cfg, gen
        # a single FaultPlan lands on shard 0; a sequence maps per shard
        plans = list(faults) if isinstance(faults, (list, tuple)) else \
            [faults] + [None] * (D - 1)
        assert len(plans) == D, (len(plans), D)
        if cfg.cache_layout == "paged":
            from .paged_engine import PagedSlotEngine
            mk = lambda *a, **k: PagedSlotEngine(  # noqa: E731
                *a, kv_pool_blocks=kv_pool_blocks, **k)
        else:
            mk = SlotEngine
        self.engines: List[SlotEngine] = [
            mk(shard_params(sm, cfg, params), cfg, gen,
               num_slots=num_slots // D, prompt_width=prompt_width,
               spec_prefix=spec_prefix, log_lenience=log_lenience,
               chunk_steps=chunk_steps, draft=draft, mesh=sm,
               faults=plan, deadline_steps=deadline_steps,
               max_queue=max_queue, overflow=overflow,
               tracer=tracer, ledger=ledger, obs_label=f"shard{i}/")
            for i, (sm, plan) in enumerate(zip(self.submeshes, plans))]
        self._rr = 0                       # round-robin submission cursor

    @property
    def num_shards(self) -> int:
        return len(self.engines)

    @property
    def responses(self) -> Dict[int, Response]:
        out: Dict[int, Response] = {}
        for e in self.engines:
            out.update(e.responses)
        return out

    # ------------------------------------------------------------- frontend

    def submit(self, req: Request) -> None:
        """Shard-local admission: the request joins one shard's FIFO queue.

        GRPO siblings (``group_id`` set) route by group so one shard owns
        the whole group — the paged engine's prompt sharing is shard-local
        (§13); everything else round-robins.  Both rules are deterministic,
        keeping kill-and-resume exact.
        """
        if req.group_id is not None:
            self.engines[req.group_id % len(self.engines)].submit(req)
            return
        self.engines[self._rr].submit(req)
        self._rr = (self._rr + 1) % len(self.engines)

    def run(self, arrivals: Optional[Iterable[Tuple[int, Request]]] = None,
            max_chunks: Optional[int] = None) -> Dict[int, Response]:
        """Drive all shard engines, interleaved chunk by chunk.

        Each engine admits from its own queue and decodes its own chunk;
        interleaving keeps the per-shard device programs in flight together
        (disjoint devices — dispatch overlaps until each shard's next
        host sync).  ``arrivals`` are routed round-robin like ``submit``
        and become due against their shard's local step counter.
        """
        subs: List[List[Tuple[int, Request]]] = [[] for _ in self.engines]
        if arrivals is not None:
            for j, (due, req) in enumerate(arrivals):
                i = req.group_id % len(self.engines) \
                    if req.group_id is not None else j % len(self.engines)
                subs[i].append((due, req))
        nxt = [iter(s) for s in subs]
        due = [next(it, None) for it in nxt]
        chunks = 0
        while True:
            moved = False
            for i, e in enumerate(self.engines):
                e._apply_faults()      # may raise EngineKilled (kind 'kill')
                while due[i] is not None and due[i][0] <= e.steps:
                    e.submit(due[i][1])
                    due[i] = next(nxt[i], None)
                e._admit()
                if not e.scheduler.idle:
                    e._run_chunk()
                    e._harvest()
                    e._enforce_deadlines()
                    moved = True
                elif due[i] is not None:
                    e.steps = max(e.steps, int(due[i][0]))  # idle fast-forward
                    moved = True
            chunks += 1
            if max_chunks is not None and chunks >= max_chunks:
                break
            if not moved:
                break
        return self.responses

    # -------------------------------------------------------------- metrics

    def metrics_registry(self) -> MetricsRegistry:
        """Type-driven merge of the shard registries (§11): the one place
        mesh gathering happens, with the merge rule carried by each
        metric's type instead of a hand-maintained key list."""
        return MetricsRegistry.merged([e.metrics_registry()
                                       for e in self.engines])

    def stats(self) -> Dict[str, float]:
        """Gathered view over the shard-local engines + per-shard dumps."""
        out = self.metrics_registry().as_dict()
        out["per_shard"] = [e.stats() for e in self.engines]
        return out

    # ----------------------------------------------- exact kill-and-resume

    def state_dict(self) -> Dict:
        """Per-shard engine snapshots plus the round-robin cursor — the
        full server future (checkpoint/io.save_server_state persists it)."""
        import numpy as np
        return {"engines": {str(i): e.state_dict()
                            for i, e in enumerate(self.engines)},
                "rr": np.int64(self._rr)}

    def load_state_dict(self, state: Dict) -> None:
        assert len(state["engines"]) == len(self.engines), \
            (len(state["engines"]), len(self.engines))
        for i, e in enumerate(self.engines):
            e.load_state_dict(state["engines"][str(i)])
        self._rr = int(state["rr"])
