"""IMPALA: asynchronous sampling + V-trace off-policy correction.

Parity: reference rllib/algorithms/impala/impala.py (async env-runner
sampling and queued learner consumption, :580-611) and the V-trace
returns of the IMPALA paper (Espeholt et al. 2018) — re-designed for the
TPU stack: instead of aggregator actors + a torch learner thread, the
driver runs one event loop that (a) keeps every env-runner actor
perpetually sampling through `foreach_actor_async`, (b) feeds a bounded
sample queue, and (c) drains the queue into a SINGLE-JIT V-trace update
(values, vtrace targets, losses, optimizer — one XLA program). Runners
act on stale weights by design; rho/c clipping corrects the off-policy
gap. Weights fan out per-runner right before each resubmission, so a
slow runner never blocks a fast one (the async property that gives
IMPALA its throughput edge over synchronous PPO).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib.algorithm_config import AlgorithmConfig
from ray_tpu.rllib.core.rl_module import ActorCriticModule, Categorical
from ray_tpu.rllib.env.env_runner import EnvRunnerConfig
from ray_tpu.rllib.env.env_runner_group import EnvRunnerGroup


@dataclasses.dataclass
class IMPALAConfig(AlgorithmConfig):
    env: str = "CartPole-v1"
    # --- rollouts (async: runners resample as soon as they finish)
    num_env_runners: int = 2
    num_envs_per_env_runner: int = 16
    rollout_length: int = 32
    # --- model
    hidden: Sequence[int] = (64, 64)
    # --- training
    lr: float = 6e-4
    gamma: float = 0.99
    vtrace_rho_clip: float = 1.0
    vtrace_c_clip: float = 1.0
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    max_grad_norm: float = 40.0
    # updates per train() call and queue bound (batches, not bytes)
    num_updates_per_iteration: int = 8
    sample_queue_size: int = 4
    broadcast_interval: int = 1   # push weights every k-th resubmission
    num_devices: int = 1          # learner dp-mesh width (see LearnerGroup)
    seed: int = 0

    def environment(self, env: str) -> "IMPALAConfig":
        self.env = env
        return self

    def env_runners(self, **kw) -> "IMPALAConfig":
        for k, v in kw.items():
            if not hasattr(self, k):
                raise ValueError(f"unknown env_runners option {k!r}")
            setattr(self, k, v)
        return self

    def training(self, **kw) -> "IMPALAConfig":
        for k, v in kw.items():
            if not hasattr(self, k):
                raise ValueError(f"unknown IMPALA training option {k!r}")
            setattr(self, k, v)
        return self

    def build(self) -> "IMPALA":
        return IMPALA(self)


def vtrace_returns(values, rewards, terms, dones, behaviour_logp,
                   target_logp, gamma, rho_clip, c_clip):
    """V-trace targets vs_t and policy-gradient advantages (no grads).

    values (T+1, N) — bootstrap value included; everything else (T, N).
    Returns (vs (T, N), pg_adv (T, N), rho_clipped (T, N)).
    """
    rho = jnp.exp(target_logp - behaviour_logp)
    rho_cl = jnp.minimum(rho_clip, rho)
    c = jnp.minimum(c_clip, rho)
    not_term = 1.0 - terms          # termination cuts the bootstrap
    not_done = 1.0 - dones          # any episode end cuts the recursion
    delta = rho_cl * (rewards + gamma * not_term * values[1:]
                      - values[:-1])

    def step(carry, inp):
        delta_t, c_t, nd_t = inp
        ws = delta_t + gamma * nd_t * c_t * carry
        return ws, ws

    _, ws = jax.lax.scan(step, jnp.zeros_like(values[0]),
                         (delta, c, not_done), reverse=True)
    vs = values[:-1] + ws
    # vs_{t+1} with the true bootstrap at the end of the fragment
    vs_tp1 = jnp.concatenate([vs[1:], values[-1:]], axis=0)
    vs_tp1 = not_done * vs_tp1 + (1.0 - not_done) * values[1:]
    pg_adv = rho_cl * (rewards + gamma * not_term * vs_tp1 - values[:-1])
    return vs, pg_adv, rho_cl


@dataclasses.dataclass
class IMPALALearnerConfig:
    obs_dim: int = 0
    num_actions: int = 0
    hidden: Sequence[int] = (64, 64)
    lr: float = 6e-4
    gamma: float = 0.99
    vtrace_rho_clip: float = 1.0
    vtrace_c_clip: float = 1.0
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    max_grad_norm: float = 40.0
    num_devices: int = 1
    seed: int = 0


class IMPALALearner:
    """Single-jit V-trace update; optional dp-mesh batch sharding."""

    # leading replicated args of the update signature before the batch
    # (APPO adds target_params and sets 3)
    N_REPLICATED_ARGS = 2

    def __init__(self, config: IMPALALearnerConfig):
        self.config = config
        self.module = ActorCriticModule(
            config.obs_dim, config.num_actions, tuple(config.hidden))
        self._tx = optax.chain(
            optax.clip_by_global_norm(config.max_grad_norm),
            optax.adam(config.lr, eps=1e-5))
        self.params = self.module.init(jax.random.PRNGKey(config.seed))
        self.opt_state = self._tx.init(self.params)
        self.version = 0
        self._timer = {"updates": 0, "update_time": 0.0, "transitions": 0}
        self._update_fn = self._jit(self._build_update())

    def _jit(self, update):
        """jit with dp-mesh batch sharding when num_devices > 1; the
        update signature is N_REPLICATED_ARGS replicated pytrees
        followed by the time-major batch."""
        config = self.config
        if config.num_devices <= 1:
            return jax.jit(update)
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devs = jax.devices()
        if len(devs) < config.num_devices:
            raise ValueError(
                f"num_devices={config.num_devices} > {len(devs)}")
        mesh = Mesh(np.array(devs[:config.num_devices]), ("dp",))
        repl = NamedSharding(mesh, P())

        def shard_for(name):
            return NamedSharding(
                mesh, P(*((None, "dp", None) if name == "obs"
                          else (None, "dp"))))
        return jax.jit(
            update,
            in_shardings=(repl,) * self.N_REPLICATED_ARGS + (
                {k: shard_for(k) for k in
                 ("obs", "actions", "logp", "rewards",
                  "terminateds", "dones", "mask")},),
            out_shardings=(repl, repl, repl))

    def _build_update(self):
        c = self.config
        module = self.module

        def loss_fn(params, batch):
            logits, value = module.forward(params, batch["obs"])
            logits = logits[:-1]                       # (T, N, A)
            logp = Categorical.log_prob(logits, batch["actions"])
            vs, pg_adv, _rho = vtrace_returns(
                jax.lax.stop_gradient(value), batch["rewards"],
                batch["terminateds"], batch["dones"], batch["logp"],
                jax.lax.stop_gradient(logp), c.gamma,
                c.vtrace_rho_clip, c.vtrace_c_clip)
            m = batch["mask"]
            denom = jnp.maximum(jnp.sum(m), 1.0)
            pg_loss = -jnp.sum(logp * pg_adv * m) / denom
            v_loss = 0.5 * jnp.sum(
                jnp.square(vs - value[:-1]) * m) / denom
            ent = jnp.sum(Categorical.entropy(logits) * m) / denom
            total = pg_loss + c.vf_coef * v_loss - c.ent_coef * ent
            return total, {"policy_loss": pg_loss, "vf_loss": v_loss,
                           "entropy": ent,
                           "mean_rho": jnp.sum(_rho * m) / denom}

        def update(params, opt_state, batch):
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            updates, opt_state = self._tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, metrics

        return update

    # ------------------------------------------------------------- api
    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        t0 = time.perf_counter()
        self.params, self.opt_state, metrics = self._update_fn(
            self.params, self.opt_state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.perf_counter() - t0
        self.version += 1
        self._timer["updates"] += 1
        self._timer["update_time"] += dt
        self._timer["transitions"] += int(np.prod(batch["rewards"].shape))
        metrics["update_time_s"] = dt
        return metrics

    def sgd_throughput(self) -> Dict[str, float]:
        t = max(self._timer["update_time"], 1e-9)
        return {"learner_transitions_per_s": self._timer["transitions"] / t,
                "updates_per_s": self._timer["updates"] / t}

    def get_weights(self):
        return jax.device_get(self.params)


class IMPALA:
    """Asynchronous trainer: runners sample continuously; each `train()`
    performs `num_updates_per_iteration` V-trace updates off the queue."""

    def __init__(self, config: IMPALAConfig):
        if config.num_env_runners < 1:
            raise ValueError("IMPALA is asynchronous: needs >=1 remote "
                             "env runner (use PPO for local debugging)")
        self.config = config
        self._probe_env()
        self.env_runner_group = EnvRunnerGroup(
            EnvRunnerConfig(
                env=config.env,
                num_envs=config.num_envs_per_env_runner,
                rollout_length=config.rollout_length,
                hidden=tuple(config.hidden),
                seed=config.seed),
            num_env_runners=config.num_env_runners)
        self.learner = self._make_learner()
        self._queue: deque = deque(maxlen=config.sample_queue_size)
        self._mgr = self.env_runner_group.manager
        self._runner_version: Dict[int, int] = {}
        self._resubmits: Dict[int, int] = {}
        self.iteration = 0
        self._total_env_steps = 0
        self._dropped_batches = 0
        self._broadcast_count = 0
        self._last_restore_probe = 0.0
        # prime the pipeline: everyone gets weights and starts sampling
        self.env_runner_group.sync_weights(self.learner.get_weights())
        for aid in self._mgr.healthy_actor_ids():
            self._runner_version[aid] = 0
            self._resubmits[aid] = 0
        self._mgr.foreach_actor_async("sample", tag="s")

    LEARNER_CLS = IMPALALearner
    LEARNER_CONFIG_CLS = IMPALALearnerConfig

    def _make_learner(self) -> "IMPALALearner":
        """Factory hook: learner-config fields mirror algorithm-config
        fields by name (APPO only swaps the two classes)."""
        kw = {f.name: getattr(self.config, f.name)
              for f in dataclasses.fields(self.LEARNER_CONFIG_CLS)
              if hasattr(self.config, f.name)}
        kw.update(obs_dim=self._obs_dim,
                  num_actions=self._num_actions,
                  hidden=tuple(self.config.hidden))
        return self.LEARNER_CLS(self.LEARNER_CONFIG_CLS(**kw))

    def _probe_env(self) -> None:
        import gymnasium as gym
        env = gym.make(self.config.env)
        self._obs_dim = int(np.prod(env.observation_space.shape))
        self._num_actions = int(env.action_space.n)
        env.close()

    # ---------------------------------------------------------- async
    def _pump(self, timeout: float = 0.0) -> None:
        """Collect finished rollouts into the queue, then keep every
        healthy runner saturated: push fresh weights (actor-call
        ordering guarantees they apply before the next rollout) and
        re-submit `sample` to any runner with nothing in flight."""
        import ray_tpu
        # Dead-runner recovery must not depend on the queue running
        # dry (a healthy majority can keep it fed forever): probe
        # unhealthy actors on a 1s cadence from the pump itself.
        if (self._mgr.num_healthy_actors < self._mgr.num_actors
                and time.time() - self._last_restore_probe > 1.0):
            self._last_restore_probe = time.time()
            self._restore_runners()
        results = self._mgr.fetch_ready_async_reqs(
            timeout_seconds=timeout, tags=["s"])
        for r in results:
            if r.ok:
                if len(self._queue) == self._queue.maxlen:
                    self._dropped_batches += 1
                self._queue.append(r.value)
        # drain completed weight-push acks so they don't pin in-flight
        self._mgr.fetch_ready_async_reqs(timeout_seconds=0.0, tags=["w"])
        weights_ref = None
        for aid in self._mgr.healthy_actor_ids():
            if self._mgr.num_in_flight(aid, tag="s") > 0:
                continue
            self._resubmits[aid] = self._resubmits.get(aid, 0) + 1
            if (self._runner_version.get(aid, -1) < self.learner.version
                    and self._resubmits[aid]
                    % self.config.broadcast_interval == 0):
                if weights_ref is None:
                    weights_ref = ray_tpu.put(self.learner.get_weights())
                n = self._mgr.foreach_actor_async(
                    "set_weights", args=(weights_ref,),
                    remote_actor_ids=[aid], tag="w")
                if n:        # skipped at in-flight cap -> retry next pump
                    self._runner_version[aid] = self.learner.version
                    self._broadcast_count += 1
            self._mgr.foreach_actor_async("sample", remote_actor_ids=[aid],
                                          tag="s")

    def _restore_runners(self) -> None:
        restored = self.env_runner_group.probe_unhealthy_env_runners()
        for aid in restored:
            self._runner_version[aid] = -1   # full weight push next pump

    # ------------------------------------------------------------ api
    def train(self) -> Dict[str, Any]:
        t0 = time.perf_counter()
        updates = 0
        learner_metrics: Dict[str, float] = {}
        # stall = 120s WITHOUT A SAMPLE, not 120s of train() wall time:
        # reset whenever the pump delivers, so long legitimate
        # iterations never trip it.
        stall_deadline = time.time() + 120.0
        while updates < self.config.num_updates_per_iteration:
            if not self._queue:
                self._pump(timeout=0.02)
                if not self._queue:
                    if time.time() > stall_deadline:
                        raise TimeoutError(
                            "IMPALA: no samples for 120s — all env "
                            "runners dead?")
                    self._restore_runners()
                    continue
                stall_deadline = time.time() + 120.0
            self._pump(timeout=0.0)      # opportunistic, non-blocking
            batch = self._queue.popleft()
            stall_deadline = time.time() + 120.0
            learner_metrics = self.learner.update(batch)
            self._total_env_steps += int(batch["mask"].sum())
            updates += 1
        self.iteration += 1
        metrics = self.env_runner_group.aggregate_metrics()
        metrics.update(learner_metrics)
        metrics.update(self.learner.sgd_throughput())
        metrics.update({
            "training_iteration": self.iteration,
            "num_env_steps_sampled_lifetime": self._total_env_steps,
            "num_learner_updates": self.learner.version,
            "num_weight_broadcasts": self._broadcast_count,
            "sample_queue_len": len(self._queue),
            "dropped_batches_lifetime": self._dropped_batches,
            "time_iteration_s": time.perf_counter() - t0,
        })
        return metrics

    def get_state(self) -> Dict[str, Any]:
        return {"params": jax.device_get(self.learner.params),
                "opt_state": jax.device_get(self.learner.opt_state),
                "iteration": self.iteration,
                "total_env_steps": self._total_env_steps}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.learner.params = jax.device_put(state["params"])
        self.learner.opt_state = jax.device_put(state["opt_state"])
        self.iteration = state.get("iteration", 0)
        self._total_env_steps = state.get("total_env_steps", 0)
        self.env_runner_group.sync_weights(self.learner.get_weights())

    def stop(self) -> None:
        self.env_runner_group.stop()


IMPALAConfig.algo_class = IMPALA
