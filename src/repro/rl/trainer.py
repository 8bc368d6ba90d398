"""RLVR trainer: GRPO / PPO / DAPO with SPEC-RL as a drop-in rollout stage.

Pipeline per step (mirrors veRL's stage order, Table 4 of the paper):
  [verification] -> [rollout] -> [assembly]   (repro.core.rollout)
  -> reward -> old-log-probs -> (values) -> adv
  -> (update-critic) -> update-actor

SPEC-RL touches ONLY the first three stages; everything downstream is the
standard algorithm — that is the paper's central compatibility claim, and the
trainer enforces it structurally (the rollout variant is a constructor
argument the update path never sees).
"""
from __future__ import annotations

import functools
import math
import random
import time
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RolloutCache, SpecConfig, rollout
from repro.core.lenience import FixedLenience
from repro.core.spec_rollout import RolloutBatch
from repro.data.dataset import PromptBatch, PromptDataset
from repro.data.tokenizer import EOS_ID, PAD_ID
from repro.distributed.mesh import (MeshConfig, shard_batch, shard_opt_state,
                                    shard_params)
from repro.engine.generate import GenerateConfig, positions_from_mask, score
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.rewards.verifier import batch_rewards

from .advantages import (gae_advantages, group_relative_advantages,
                         terminal_reward_to_tokens, whiten)
from .critic import forward_values, init_critic
from .losses import (PolicyLossConfig, entropy_bonus, kl_to_reference,
                     masked_mean, policy_loss, value_loss)


@dataclass(frozen=True)
class RLConfig:
    algo: str = "grpo"                # grpo|ppo|dapo
    group_size: int = 4
    prompts_per_batch: int = 8
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_p: float = 1.0
    optim: adamw.AdamWConfig = adamw.AdamWConfig(lr=5e-7)
    critic_optim: adamw.AdamWConfig = adamw.AdamWConfig(lr=1e-5)
    gamma: float = 1.0
    gae_lambda: float = 0.95
    whiten_adv: bool = False
    dynamic_sampling: bool = True     # DAPO only
    max_resample_rounds: int = 3
    entropy_coef: float = 0.0

    def policy_cfg(self) -> PolicyLossConfig:
        if self.algo == "dapo":
            return PolicyLossConfig(clip_low=0.2, clip_high=0.28, clip_c=10.0,
                                    agg="token", kl_coef=0.0,
                                    entropy_coef=self.entropy_coef)
        if self.algo == "grpo":
            return PolicyLossConfig(clip_low=0.2, clip_high=0.2, clip_c=3.0,
                                    agg="seq", kl_coef=1e-4,
                                    entropy_coef=self.entropy_coef)
        return PolicyLossConfig(clip_low=0.2, clip_high=0.2, clip_c=3.0,
                                agg="seq", kl_coef=0.0,
                                entropy_coef=self.entropy_coef)


# ------------------------------------------------------------------ jit steps


@functools.partial(jax.jit, static_argnames=("cfg", "resp_start",
                                             "temperature", "top_p"))
def _old_logprobs(params, cfg, full_tokens, full_mask, resp_start: int,
                  temperature: float, top_p: float):
    sc = score(params, cfg, full_tokens, full_mask, temperature=temperature,
               top_p=top_p, return_entropy=True)
    return (sc["logprobs"][:, resp_start:], sc["entropy"][:, resp_start:])


def _actor_loss_fn(params, cfg, pcfg: PolicyLossConfig, full_tokens, full_mask,
                   resp_start, lp_old, advantages, resp_mask, ref_lp,
                   temperature, top_p, moe_lb_coef, moe_z_coef):
    from repro.engine.sampling import entropy_of, logprobs_of
    positions = positions_from_mask(full_mask)
    logits, aux = M.forward(params, cfg, full_tokens, positions)
    lp_next = logprobs_of(logits[:, :-1], full_tokens[:, 1:], temperature, top_p)
    lp_all = jnp.concatenate([jnp.zeros_like(lp_next[:, :1]), lp_next], axis=1)
    ent_next = entropy_of(logits[:, :-1], temperature)
    ent_all = jnp.concatenate([jnp.zeros_like(ent_next[:, :1]), ent_next], axis=1)
    lp_new = lp_all[:, resp_start:]
    ent = ent_all[:, resp_start:]
    loss, info = policy_loss(lp_new, lp_old, advantages, resp_mask, pcfg)
    if pcfg.kl_coef > 0.0:
        kl = kl_to_reference(lp_new, ref_lp, resp_mask)
        loss = loss + pcfg.kl_coef * kl
        info["kl_ref"] = kl
    if pcfg.entropy_coef > 0.0:
        loss = loss - pcfg.entropy_coef * entropy_bonus(ent, resp_mask)
    if "moe_lb_loss" in aux:  # MoE aux losses (if the arch has them)
        loss = loss + moe_lb_coef * aux["moe_lb_loss"] + \
            moe_z_coef * aux["moe_z_loss"]
        info["moe_lb_loss"] = aux["moe_lb_loss"]
    info["entropy"] = masked_mean(ent, resp_mask)
    return loss, info


# The optimizer state is donated: its float32 moments are the largest
# buffers of the step (twice the bf16 parameters), and without donation the
# old and new moments coexist at the step's peak.
@functools.partial(jax.jit, static_argnames=("cfg", "pcfg", "ocfg", "resp_start",
                                             "temperature", "top_p"),
                   donate_argnames=("opt_state",))
def _update_actor(params, opt_state, cfg, pcfg, ocfg, full_tokens, full_mask,
                  resp_start, lp_old, advantages, resp_mask, ref_lp,
                  temperature, top_p):
    (loss, info), grads = jax.value_and_grad(_actor_loss_fn, has_aux=True)(
        params, cfg, pcfg, full_tokens, full_mask, resp_start, lp_old,
        advantages, resp_mask, ref_lp, temperature, top_p,
        cfg.router_aux_coef, cfg.router_z_coef)
    params, opt_state, oinfo = adamw.update(ocfg, params, grads, opt_state)
    info.update(oinfo)
    info["loss"] = loss
    return params, opt_state, info


@functools.partial(jax.jit, static_argnames=("cfg", "ocfg", "resp_start"),
                   donate_argnames=("copt_state",))
def _update_critic(cparams, copt_state, cfg, ocfg, full_tokens, full_mask,
                   resp_start, returns, old_values, resp_mask):
    def loss_fn(p):
        v = forward_values(p, cfg, full_tokens, full_mask)[:, resp_start:]
        return value_loss(v, returns, old_values, resp_mask)

    loss, grads = jax.value_and_grad(loss_fn)(cparams)
    cparams, copt_state, oinfo = adamw.update(ocfg, cparams, grads, copt_state)
    return cparams, copt_state, {"critic_loss": loss, **oinfo}


# ------------------------------------------------------------------ collector


class Collector:
    """The collection half of the RL loop (DESIGN.md §12): dataset
    sampling, the SPEC-RL rollout cache, the lenience schedule, the
    collection PRNG stream and the DAPO dynamic-sampling resample loop —
    everything ``train_step`` needs to turn params into a rewarded batch,
    and nothing it needs to *update* them.

    The synchronous ``Trainer`` drives it in-process; the async rollout
    service (serving/rollout_service.py) drives the *same object* from the
    producer side of the disaggregated seam.  Both topologies therefore
    share one definition of a collect step — same sampling RNG, same PRNG
    split order, same cache — which is what makes the K=0 deterministic
    schedule token-identical to the synchronous path (the §12 determinism
    contract)."""

    def __init__(self, model_cfg: ModelConfig, rl: RLConfig, spec: SpecConfig,
                 dataset: PromptDataset, key, lenience_schedule=None,
                 mesh=None, tracer=None):
        self.cfg = model_cfg
        self.rl = rl
        self.spec = spec
        # lenience schedule (fixed / warmup / adaptive); adaptive closes the
        # paper's future-work item by steering |approx_kl| to a budget
        self.lenience_schedule = lenience_schedule or FixedLenience(
            spec.lenience)
        self.dataset = dataset
        self.mesh = mesh
        self.key = key
        # group_size makes the cache sibling-aware: the dataset keys slot g
        # of prompt p as p*G + g, so the §9 draft engine can index a row's
        # GRPO siblings as its n-gram corpus (cache.siblings)
        self.cache = RolloutCache(history=spec.cache_history,
                                  max_prompts=spec.cache_max_prompts,
                                  group_size=rl.group_size)
        self.gen = GenerateConfig(max_new_tokens=rl.max_new_tokens,
                                  temperature=rl.temperature, top_p=rl.top_p,
                                  eos_id=EOS_ID, pad_id=PAD_ID)
        self.gen_steps = 0            # DAPO: generation steps consumed
        self.total_generated_tokens = 0
        self._py_rng = random.Random(1234)
        self._tracer = tracer
        self.batches = 0              # collect calls: the spans' batch id

    # ---------------------------------------------------------------- §11

    @property
    def tracer(self):
        """The tracer given at construction, else the process-global one
        at the time of the call (so ``obs.configure`` reaches a live
        collector)."""
        from repro.obs import get_tracer
        return self._tracer if self._tracer is not None else get_tracer()

    @staticmethod
    def _stage(name: str, t0: float, times: Dict[str, float],
               key: str) -> None:
        """Close a collect stage: record its duration under ``key`` and a
        train.* histogram sample (its span is scoped around the stage)."""
        from repro.obs import get_registry
        times[key] = time.perf_counter() - t0
        get_registry().observe(f"train.{name}_s", times[key])

    # -------------------------------------------------------------- rollout

    def sample(self, epoch: int,
               batch: Optional[PromptBatch] = None) -> PromptBatch:
        """Epoch-keyed batch draw from the shared python RNG stream (the
        stream both topologies replay in lockstep)."""
        if batch is not None:
            return batch
        return self.dataset.sample_batch(self._py_rng,
                                         self.rl.prompts_per_batch,
                                         self.rl.group_size, epoch=epoch)

    def rollout_once(self, params, batch: PromptBatch,
                     epoch: int) -> RolloutBatch:
        self.key, sub = jax.random.split(self.key)
        cur_l = float(self.lenience_schedule(epoch))
        if cur_l != self.spec.lenience and self.spec.variant == "spec":
            self.spec = replace(self.spec, lenience=cur_l)
        rb = rollout(params, self.cfg, self.gen, self.spec,
                     jnp.asarray(batch.tokens), jnp.asarray(batch.mask),
                     batch.cache_keys, self.cache, sub, epoch,
                     mesh=self.mesh)
        self.gen_steps += 1
        self.total_generated_tokens += rb.metrics["n_generated"]
        return rb

    def collect(self, params, batch: PromptBatch, epoch: int
                ) -> Tuple[PromptBatch, RolloutBatch, np.ndarray,
                           Dict[str, float]]:
        """Rollout + reward (+ DAPO dynamic sampling) under ``params``:
        the ``trainer.collect`` span, whose children (``rollout.*``,
        ``trainer.reward``) share its ``batch`` id."""
        tr = self.tracer
        self.batches += 1
        with tr.span("collect", "trainer", cat="train", step=epoch,
                     batch=self.batches):
            t0 = time.perf_counter()
            rb = self.rollout_once(params, batch, epoch)
            with tr.span("reward", "trainer", cat="train"):
                t_reward0 = time.perf_counter()
                rewards = batch_rewards(rb.response, rb.length,
                                        batch.answers)
                rtimes: Dict[str, float] = {}
                self._stage("reward", t_reward0, rtimes, "reward_time")
            reward_time = rtimes["reward_time"]

            if self.rl.algo == "dapo" and self.rl.dynamic_sampling:
                G = self.rl.group_size
                for _ in range(self.rl.max_resample_rounds):
                    g = rewards.reshape(-1, G)
                    degenerate = (g.std(axis=1) == 0.0)
                    if not degenerate.any():
                        break
                    # resample the degenerate prompt groups with fresh
                    # rollouts
                    idxs = np.where(degenerate)[0]
                    sub_batch = _subset_batch(batch, idxs, G)
                    rb2 = self.rollout_once(params, sub_batch, epoch)
                    with tr.span("reward", "trainer", cat="train"):
                        r2 = batch_rewards(rb2.response, rb2.length,
                                           sub_batch.answers)
                    rb = _merge_rollouts(rb, rb2, idxs, G)
                    rewards = rewards.copy()
                    for j, gi in enumerate(idxs):
                        rewards[gi * G:(gi + 1) * G] = r2[j * G:(j + 1) * G]

            stage_times = dict(rb.metrics)
            stage_times["reward_time"] = reward_time
            self._stage("collect", t0, stage_times, "collect_time")
        return batch, rb, rewards, stage_times


# ------------------------------------------------------------------ trainer


class Trainer:
    def __init__(self, model_cfg: ModelConfig, rl: RLConfig, spec: SpecConfig,
                 dataset: PromptDataset, key,
                 critic_cfg: Optional[ModelConfig] = None,
                 lenience_schedule=None, mesh=None, watchdog=None,
                 tracer=None, alerts=None):
        self.cfg = model_cfg
        self.rl = rl
        # mesh (DESIGN.md §8): a MeshConfig (or prebuilt Mesh) shards params
        # and optimizer moments by the param_spec rules and batch rows over
        # the data axes; rollout AND the update steps then compile SPMD on
        # one mesh with no host round-trips between stages.  ``None`` (or a
        # config that does not fit the host's devices) is the single-device
        # path, token-identical by the §8 contract.
        if isinstance(mesh, MeshConfig):
            mesh = mesh.build()
        self.mesh = mesh
        k1, k2, k3, coll_key = jax.random.split(key, 4)
        # §12: collection state lives in the Collector — the synchronous
        # path drives it here, the async rollout service drives the same
        # object from the producer side
        self.collector = Collector(model_cfg, rl, spec, dataset, coll_key,
                                   lenience_schedule=lenience_schedule,
                                   mesh=mesh, tracer=tracer)
        self.params = shard_params(mesh, model_cfg, M.init_lm(k1, model_cfg))
        self.opt_state = shard_opt_state(mesh, model_cfg, self.params,
                                         adamw.init(self.params))
        self.pcfg = rl.policy_cfg()
        self.ref_params = shard_params(
            mesh, model_cfg, jax.tree.map(jnp.copy, self.params)) \
            if self.pcfg.kl_coef > 0 else None
        self.critic_cfg = critic_cfg or model_cfg
        if rl.algo == "ppo":
            self.critic_params = shard_params(
                mesh, self.critic_cfg, init_critic(k2, self.critic_cfg))
            self.critic_opt_state = shard_opt_state(
                mesh, self.critic_cfg, self.critic_params,
                adamw.init(self.critic_params))
        else:
            self.critic_params = None
        self.step_idx = 0
        self.history: List[Dict[str, float]] = []
        # §10 watchdog (rl/watchdog.py): snapshots on healthy steps,
        # restore-last-good + skip-the-batch on non-finite loss or a
        # stalled rollout stage.  None = no monitoring (the default).
        self.watchdog = watchdog
        # §14 alerts (obs/alerts.py): an AlertManager evaluated on every
        # step's flat metrics; events trace on the 'alerts' lane and, when
        # a watchdog rides along, feed its degradation counters.
        self.alerts = alerts
        if alerts is not None and alerts.watchdog is None:
            alerts.watchdog = watchdog
        # §11 observatory: stage spans land on the 'trainer' lane; stage
        # latencies feed train.* histograms in the global registry.  The
        # default NULL_TRACER records nothing and every stamp below reuses
        # a perf_counter reading the times dict already takes.
        from repro.obs import get_tracer
        self.tracer = tracer if tracer is not None else get_tracer()
        self.last_rb: Optional[RolloutBatch] = None

    # ------------------------------------------- collection-state delegation
    # The watchdog snapshot/restore path, tests and benches address
    # collection state through the trainer (tr.cache, tr.key, ...); the
    # state itself lives in the Collector so the async topology can share
    # it.  Plain delegating properties keep both views one object.

    @property
    def spec(self) -> SpecConfig:
        return self.collector.spec

    @spec.setter
    def spec(self, v) -> None:
        self.collector.spec = v

    @property
    def dataset(self) -> PromptDataset:
        return self.collector.dataset

    @property
    def gen(self) -> GenerateConfig:
        return self.collector.gen

    @property
    def lenience_schedule(self):
        return self.collector.lenience_schedule

    @property
    def cache(self) -> RolloutCache:
        return self.collector.cache

    @cache.setter
    def cache(self, v) -> None:
        self.collector.cache = v

    @property
    def key(self):
        return self.collector.key

    @key.setter
    def key(self, v) -> None:
        self.collector.key = v

    @property
    def gen_steps(self) -> int:
        return self.collector.gen_steps

    @gen_steps.setter
    def gen_steps(self, v) -> None:
        self.collector.gen_steps = v

    @property
    def total_generated_tokens(self):
        return self.collector.total_generated_tokens

    @total_generated_tokens.setter
    def total_generated_tokens(self, v) -> None:
        self.collector.total_generated_tokens = v

    @property
    def _py_rng(self) -> random.Random:
        return self.collector._py_rng

    # ---------------------------------------------------------------- §11

    def _stage(self, name: str, t0: float, times: Dict[str, float],
               key: str) -> float:
        """Close a trainer stage: record its duration under ``key``, emit a
        'trainer'-lane span and a train.* histogram sample.  Returns the end
        stamp (= the next stage's natural start)."""
        from repro.obs import get_registry
        t1 = time.perf_counter()
        times[key] = t1 - t0
        if self.tracer.enabled:
            self.tracer.complete(name, "trainer", t0, t1, cat="train",
                                 step=self.step_idx)
        get_registry().observe(f"train.{name}_s", t1 - t0)
        return t1

    # -------------------------------------------------------------- rollout

    def _collect(self, batch: PromptBatch) -> Tuple[PromptBatch, RolloutBatch,
                                                    np.ndarray, Dict[str, float]]:
        """Rollout + reward (+ DAPO dynamic sampling) — the in-process
        (synchronous) drive of the shared Collector."""
        return self.collector.collect(self.params, batch, self.step_idx)

    # -------------------------------------------------------------- training
    def train_step(self, batch: Optional[PromptBatch] = None) -> Dict[str, float]:
        batch = self.collector.sample(self.step_idx, batch)
        t_step0 = time.perf_counter()
        batch, rb, rewards, times = self._collect(batch)
        return self.optimize(rb, rewards, times, t_step0=t_step0)

    def optimize(self, rb: RolloutBatch, rewards: np.ndarray,
                 times: Dict[str, float], *, behaviour_lp=None,
                 is_clip: Optional[float] = None,
                 extra_metrics: Optional[Dict[str, float]] = None,
                 t_step0: Optional[float] = None) -> Dict[str, float]:
        """The optimization half of ``train_step``: old-logprobs → (ref) →
        advantages → (critic) → actor update, on an already-collected and
        already-rewarded rollout.

        The synchronous path calls it back-to-back with ``_collect``; the
        async consumer (rl/async_loop.py) calls it on buffered
        trajectories.  ``behaviour_lp`` (with cap ``is_clip``) switches on
        the §12 truncated-importance-weight correction for trajectories up
        to K versions stale; ``None`` — the synchronous default — leaves
        the update bit-identical to the pre-split trainer."""
        if t_step0 is None:
            t_step0 = time.perf_counter()
        self.last_rb = rb
        B, P = rb.prompt.shape
        N = rb.response.shape[1]

        full_tokens = jnp.asarray(np.concatenate([rb.prompt, rb.response], 1))
        full_mask = jnp.asarray(np.concatenate([rb.prompt_mask,
                                                rb.response_mask], 1))
        resp_mask = jnp.asarray(rb.response_mask)
        lengths = jnp.asarray(rb.length)
        rew = jnp.asarray(rewards)
        if self.mesh is not None:
            # batch rows over the data axes: old-logprob / value / update
            # steps compile SPMD against the sharded params — rollout and
            # train run on the same mesh with no host re-layout between
            full_tokens, full_mask, resp_mask, lengths, rew = shard_batch(
                self.mesh, (full_tokens, full_mask, resp_mask, lengths, rew))

        # ---- old log-probs (veRL stage; ratio == 1 at the first epoch) ----
        t0 = time.perf_counter()
        lp_old, ent_old = _old_logprobs(self.params, self.cfg, full_tokens,
                                        full_mask, P, self.rl.temperature,
                                        self.rl.top_p)
        lp_old = jax.block_until_ready(lp_old)
        self._stage("old_logprob", t0, times, "old_logprob_time")

        ref_lp = jnp.zeros_like(lp_old)
        if self.ref_params is not None:
            t0 = time.perf_counter()
            ref_lp, _ = _old_logprobs(self.ref_params, self.cfg, full_tokens,
                                      full_mask, P, self.rl.temperature,
                                      self.rl.top_p)
            ref_lp = jax.block_until_ready(ref_lp)
            self._stage("ref", t0, times, "ref_time")

        # ---- advantages ----------------------------------------------------
        t0 = time.perf_counter()
        old_values = returns = None
        if self.rl.algo == "ppo":
            tv = time.perf_counter()
            values = forward_values(self.critic_params, self.critic_cfg,
                                    full_tokens, full_mask)[:, P:]
            self._stage("values", tv, times, "values_time")
            rew_tok = terminal_reward_to_tokens(rew, lengths, N)
            adv, returns = gae_advantages(rew_tok, values, resp_mask,
                                          gamma=self.rl.gamma,
                                          lam=self.rl.gae_lambda)
            old_values = values
            if self.rl.whiten_adv:
                adv = whiten(adv, resp_mask)
        else:
            scalar_adv = group_relative_advantages(rew, self.rl.group_size)
            adv = scalar_adv[:, None] * resp_mask.astype(jnp.float32)
        if behaviour_lp is not None:
            # §12 bounded-staleness correction: the trajectory was sampled
            # under an older policy, so the PPO ratio's anchor (lp_old,
            # scored under the *current* params) is off-policy relative to
            # the behaviour distribution.  Truncated per-token importance
            # weights w = min(ρ̄, exp(lp_now − lp_behaviour)) fold into the
            # advantages — losses.policy_loss sees its standard inputs, so
            # the paper's compatibility claim extends across the async seam.
            blp = jnp.asarray(behaviour_lp)
            if self.mesh is not None:
                blp = shard_batch(self.mesh, blp)
            cap = float(is_clip) if is_clip is not None else 2.0
            w = jnp.minimum(cap, jnp.exp(lp_old - blp)) \
                * resp_mask.astype(jnp.float32)
            adv = adv * w
            times["is_weight_mean"] = float(masked_mean(w, resp_mask))
        self._stage("adv", t0, times, "adv_time")

        # ---- updates -------------------------------------------------------
        if self.rl.algo == "ppo":
            t0 = time.perf_counter()
            self.critic_params, self.critic_opt_state, cinfo = _update_critic(
                self.critic_params, self.critic_opt_state, self.critic_cfg,
                self.rl.critic_optim, full_tokens, full_mask, P, returns,
                old_values, resp_mask)
            self._stage("update_critic", t0, times, "update_critic_time")
        else:
            cinfo = {}

        t0 = time.perf_counter()
        self.params, self.opt_state, info = _update_actor(
            self.params, self.opt_state, self.cfg, self.pcfg, self.rl.optim,
            full_tokens, full_mask, P, lp_old, adv, resp_mask, ref_lp,
            self.rl.temperature, self.rl.top_p)
        jax.block_until_ready(info["loss"])
        t_end = self._stage("update_actor", t0, times, "update_actor_time")
        from repro.obs import get_registry
        get_registry().observe("train.train_step_s", t_end - t_step0)
        if self.tracer.enabled:
            # whole-step span encloses the stage spans on the same lane
            self.tracer.complete("train_step", "trainer", t_step0, t_end,
                                 cat="train", step=self.step_idx)

        self.lenience_schedule.update(abs(float(info.get("approx_kl", 0.0))))
        metrics = {
            "step": self.step_idx,
            "lenience": float(self.spec.lenience),
            "reward_mean": float(rewards.mean()),
            "response_len_mean": float(np.asarray(rb.length).mean()),
            "total_generated_tokens": self.total_generated_tokens,
            "gen_steps": self.gen_steps,
            **{k: float(v) for k, v in info.items()},
            **{k: float(v) for k, v in cinfo.items()},
            **{k: float(v) for k, v in times.items() if isinstance(v, (int, float))},
        }
        if extra_metrics:
            # async-loop provenance (staleness, buffer counters, mode) joins
            # the flat namespace BEFORE the watchdog sees the step
            metrics.update({k: float(v) for k, v in extra_metrics.items()})
        # §11 schema fix: the step log is routed through a MetricsRegistry
        # so the trainer shares the audited flat-float namespace with
        # SlotEngine.stats()/MeshSlotServer.stats() (one as_dict view, no
        # ad-hoc key drift between surfaces)
        from repro.obs import (MetricsRegistry, get_decision_log, get_ledger)
        led = get_ledger()
        if led.enabled:
            # §14: cumulative provenance counts join the step log — the
            # savings-attribution report divides exactly these numbers —
            # and mirror into the global registry so the events.jsonl dump
            # feeds `launch.analysis attrib` offline
            from repro.obs import get_registry
            greg = get_registry()
            for cname, nv in led.counts_dict().items():
                metrics[f"ledger_tokens_{cname}"] = float(nv)
                greg.set(f"ledger.tokens_{cname}", float(nv), agg="max")
            metrics["ledger_finalized"] = float(led.finalized)
            metrics["ledger_violations"] = float(led.violations)
        metrics = MetricsRegistry.from_flat(metrics).as_dict()
        if self.alerts is not None:
            # evaluated on the flat step metrics BEFORE the watchdog so a
            # critical alert's counters are visible to the same step log
            self.alerts.evaluate(metrics, self.step_idx)
            metrics.update(self.alerts.as_dict())
        if self.watchdog is not None:
            # may restore params/opt_state/cache to the last snapshot (the
            # poisoned update is undone; step_idx still advances below, so
            # the bad batch is skipped, not replayed) — and always folds
            # its counters into the step metrics
            self.watchdog.after_step(self, metrics)
        dec = get_decision_log()
        if dec.enabled:
            # decision shards hit disk once per train step, not per record
            dec.flush()
        self.history.append(metrics)
        self.step_idx += 1
        return metrics

    def train(self, num_steps: int, log_every: int = 10,
              callback=None) -> List[Dict[str, float]]:
        for _ in range(num_steps):
            m = self.train_step()
            if callback and (m["step"] % log_every == 0):
                callback(m)
        return self.history


# ------------------------------------------------------------------ helpers


def _subset_batch(batch: PromptBatch, group_idxs: np.ndarray, G: int
                  ) -> PromptBatch:
    rows = np.concatenate([np.arange(g * G, (g + 1) * G) for g in group_idxs])
    return PromptBatch(
        tokens=batch.tokens[rows], mask=batch.mask[rows],
        cache_keys=[batch.cache_keys[r] for r in rows],
        answers=[batch.answers[r] for r in rows],
        problem_ids=[batch.problem_ids[r] for r in rows],
        epoch=batch.epoch)


def _merge_rollouts(rb: RolloutBatch, rb2: RolloutBatch, group_idxs: np.ndarray,
                    G: int) -> RolloutBatch:
    rows = np.concatenate([np.arange(g * G, (g + 1) * G) for g in group_idxs])
    out = RolloutBatch(
        prompt=rb.prompt.copy(), prompt_mask=rb.prompt_mask.copy(),
        response=rb.response.copy(), response_mask=rb.response_mask.copy(),
        behaviour_logprobs=rb.behaviour_logprobs.copy(),
        length=rb.length.copy(), metrics=dict(rb.metrics))
    out.response[rows] = rb2.response
    out.response_mask[rows] = rb2.response_mask
    out.behaviour_logprobs[rows] = rb2.behaviour_logprobs
    out.length[rows] = rb2.length
    for k in ("n_generated", "n_reused"):
        out.metrics[k] = rb.metrics.get(k, 0) + rb2.metrics.get(k, 0)
    return out
