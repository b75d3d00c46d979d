"""SAC: off-policy maximum-entropy actor-critic for continuous control.

Parity: reference rllib/algorithms/sac/sac.py (+ default_sac_rl_module /
sac_learner) — twin Q critics with target networks, squashed-Gaussian
policy, and entropy-coefficient autotuning toward a target entropy —
re-designed for this stack like DQN: flat-transition env runners feed a
replay buffer and ONE jitted update performs the critic, actor, and
alpha steps plus the polyak target update in a single XLA program.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib.algorithm_config import AlgorithmConfig

from ray_tpu.rllib.utils.replay_buffers import ReplayBuffer

_LOG_STD_MIN, _LOG_STD_MAX = -20.0, 2.0
_EPS = 1e-6


def _mlp_init(key, dims, out_scale=1.0):
    layers = []
    keys = jax.random.split(key, len(dims) - 1)
    for i, k in enumerate(keys):
        din, dout = dims[i], dims[i + 1]
        scale = out_scale if i == len(keys) - 1 else float(np.sqrt(2.0))
        w = jax.random.orthogonal(k, max(din, dout))[:din, :dout]
        layers.append({"w": (w * scale).astype(jnp.float32),
                       "b": jnp.zeros((dout,), jnp.float32)})
    return layers


def _mlp(layers, x, act=jnp.tanh):
    for layer in layers[:-1]:
        x = act(x @ layer["w"] + layer["b"])
    last = layers[-1]
    return x @ last["w"] + last["b"]


@dataclasses.dataclass(frozen=True)
class SACModule:
    """Squashed-Gaussian policy + twin Q critics (reference
    default_sac_rl_module.py)."""

    obs_dim: int
    act_dim: int
    hidden: Sequence[int] = (256, 256)

    def init(self, key: jax.Array) -> dict:
        kp, k1, k2 = jax.random.split(key, 3)
        h = list(self.hidden)
        return {
            "pi": _mlp_init(kp, [self.obs_dim] + h + [2 * self.act_dim],
                            out_scale=0.01),
            "q1": _mlp_init(k1, [self.obs_dim + self.act_dim] + h + [1]),
            "q2": _mlp_init(k2, [self.obs_dim + self.act_dim] + h + [1]),
        }

    # ------------------------------------------------------------ policy
    def pi_dist(self, params, obs):
        out = _mlp(params["pi"], obs)
        mean, log_std = jnp.split(out, 2, axis=-1)
        log_std = jnp.clip(log_std, _LOG_STD_MIN, _LOG_STD_MAX)
        return mean, log_std

    def sample_action(self, params, obs, key):
        """Reparameterized squashed sample -> (action in [-1,1], logp)."""
        mean, log_std = self.pi_dist(params, obs)
        std = jnp.exp(log_std)
        u = mean + std * jax.random.normal(key, mean.shape)
        a = jnp.tanh(u)
        logp_u = jnp.sum(
            -0.5 * jnp.square((u - mean) / std) - log_std
            - 0.5 * jnp.log(2 * jnp.pi), axis=-1)
        # tanh change of variables (SAC paper appendix C)
        logp = logp_u - jnp.sum(jnp.log(1 - jnp.square(a) + _EPS),
                                axis=-1)
        return a, logp

    # ------------------------------------------------------------ critic
    @staticmethod
    def q(params_q, obs, act):
        return _mlp(params_q, jnp.concatenate([obs, act], -1),
                    act=jax.nn.relu)[..., 0]


class SACEnvRunner:
    """Vectorized continuous sampler emitting flat transitions; actions
    are squashed-Gaussian samples scaled to the env bounds."""

    def __init__(self, config: "SACConfig", worker_index: int = 0):
        import gymnasium as gym
        self.config = config
        seed = config.seed + 1000 * worker_index
        self._envs = gym.make_vec(config.env,
                                  num_envs=config.num_envs_per_env_runner,
                                  vectorization_mode="sync")
        space = self._envs.single_action_space
        if hasattr(space, "n"):
            raise ValueError("SAC needs a continuous (Box) action space")
        self._low = np.asarray(space.low, np.float32)
        self._high = np.asarray(space.high, np.float32)
        self.module = SACModule(
            int(np.prod(self._envs.single_observation_space.shape)),
            int(np.prod(space.shape)), tuple(config.hidden))
        self.params = jax.tree_util.tree_map(
            np.asarray, self.module.init(jax.random.PRNGKey(seed)))
        self._rng = np.random.default_rng(seed + 1)
        self._obs, _ = self._envs.reset(seed=seed)
        self._prev_done = np.zeros(config.num_envs_per_env_runner, bool)
        self._steps = 0
        self._ep_ret = np.zeros(config.num_envs_per_env_runner)
        self._recent: list = []

    def ping(self):
        return "pong"

    def set_weights(self, weights) -> None:
        self.params = jax.tree_util.tree_map(np.asarray, weights)

    def _policy_np(self, obs):
        x = obs
        for layer in self.params["pi"][:-1]:
            x = np.tanh(x @ layer["w"] + layer["b"])
        last = self.params["pi"][-1]
        out = x @ last["w"] + last["b"]
        mean, log_std = np.split(out, 2, axis=-1)
        return mean, np.clip(log_std, _LOG_STD_MIN, _LOG_STD_MAX)

    def sample(self, num_steps: int) -> Dict[str, np.ndarray]:
        c = self.config
        rows = {k: [] for k in ("obs", "actions", "rewards", "new_obs",
                                "terminateds")}
        N = c.num_envs_per_env_runner
        for _ in range(num_steps):
            obs32 = self._obs.astype(np.float32)
            if self._steps < c.random_steps:
                a = self._rng.uniform(-1.0, 1.0,
                                      (N, self.module.act_dim))
            else:
                mean, log_std = self._policy_np(obs32)
                u = mean + np.exp(log_std) * self._rng.standard_normal(
                    mean.shape)
                a = np.tanh(u)
            env_a = (self._low + (a.astype(np.float32) + 1.0)
                     * 0.5 * (self._high - self._low))
            nobs, reward, term, trunc, _ = self._envs.step(env_a)
            done = term | trunc
            valid = ~self._prev_done       # autoreset filler: drop
            rows["obs"].append(obs32[valid])
            rows["actions"].append(a[valid].astype(np.float32))
            rows["rewards"].append(reward[valid].astype(np.float32))
            rows["new_obs"].append(nobs[valid].astype(np.float32))
            rows["terminateds"].append(term[valid].astype(np.float32))
            self._ep_ret[valid] += reward[valid]
            for i in np.nonzero(done & valid)[0]:
                self._recent.append(float(self._ep_ret[i]))
                self._ep_ret[i] = 0.0
            self._recent = self._recent[-100:]
            self._prev_done = done
            self._obs = nobs
            self._steps += N
        return {k: np.concatenate(v) for k, v in rows.items()}

    def get_metrics(self) -> Dict[str, Any]:
        return {"episode_return_mean": (float(np.mean(self._recent))
                                        if self._recent else float("nan")),
                "num_episodes": len(self._recent),
                "num_env_steps_sampled": self._steps}

    def stop(self) -> None:
        self._envs.close()


@dataclasses.dataclass
class SACConfig(AlgorithmConfig):
    env: str = "Pendulum-v1"
    num_env_runners: int = 0               # 0 = local
    num_envs_per_env_runner: int = 8
    rollout_steps_per_iteration: int = 32
    hidden: Sequence[int] = (256, 256)
    actor_lr: float = 3e-4
    critic_lr: float = 3e-4
    alpha_lr: float = 3e-4
    gamma: float = 0.99
    tau: float = 0.005                     # polyak target rate
    initial_alpha: float = 0.2
    target_entropy: float | None = None    # default: -act_dim
    buffer_size: int = 100_000
    train_batch_size: int = 256
    num_updates_per_iteration: int = 256
    learning_starts: int = 1_000           # env steps before updates
    random_steps: int = 1_000              # uniform exploration warmup
    seed: int = 0

class SAC:
    """Iterative trainer: sample -> buffer -> k SAC updates (critic +
    actor + alpha + polyak in one jit)."""

    def __init__(self, config: SACConfig):
        self.config = config
        c = config
        if c.num_env_runners == 0:
            self._runners = [SACEnvRunner(c)]
            self._remote = False
        else:
            import ray_tpu
            cls = ray_tpu.remote(num_cpus=1)(SACEnvRunner)
            self._runners = [cls.remote(c, worker_index=i + 1)
                             for i in range(c.num_env_runners)]
            self._remote = True
        obs_dim, act_dim = self._probe_dims()
        self.module = SACModule(obs_dim, act_dim, tuple(c.hidden))
        key = jax.random.PRNGKey(c.seed)
        key, init_key = jax.random.split(key)
        self._key = key
        self.params = self.module.init(init_key)
        self.target_q = {"q1": jax.tree_util.tree_map(
                             jnp.copy, self.params["q1"]),
                         "q2": jax.tree_util.tree_map(
                             jnp.copy, self.params["q2"])}
        self.log_alpha = jnp.asarray(
            np.log(c.initial_alpha), jnp.float32)
        self._target_entropy = (c.target_entropy
                                if c.target_entropy is not None
                                else -float(act_dim))
        self._actor_tx = optax.adam(c.actor_lr)
        self._critic_tx = optax.adam(c.critic_lr)
        self._alpha_tx = optax.adam(c.alpha_lr)
        self._actor_opt = self._actor_tx.init(self.params["pi"])
        self._critic_opt = self._critic_tx.init(
            {"q1": self.params["q1"], "q2": self.params["q2"]})
        self._alpha_opt = self._alpha_tx.init(self.log_alpha)
        self.buffer = ReplayBuffer(c.buffer_size, seed=c.seed)
        self._update_fn = jax.jit(self._build_update())
        self._num_updates = 0
        self._total_steps = 0
        self.iteration = 0

    def _probe_dims(self) -> Tuple[int, int]:
        import gymnasium as gym
        env = gym.make(self.config.env)
        dims = (int(np.prod(env.observation_space.shape)),
                int(np.prod(env.action_space.shape)))
        env.close()
        return dims

    def _build_update(self):
        c = self.config
        module = self.module

        def critic_loss_fn(q_params, params, target_q, log_alpha,
                           batch, key):
            next_a, next_logp = module.sample_action(
                params, batch["new_obs"], key)
            tq = jnp.minimum(
                module.q(target_q["q1"], batch["new_obs"], next_a),
                module.q(target_q["q2"], batch["new_obs"], next_a))
            alpha = jnp.exp(log_alpha)
            y = batch["rewards"] + c.gamma * (1 - batch["terminateds"]) \
                * jax.lax.stop_gradient(tq - alpha * next_logp)
            y = jax.lax.stop_gradient(y)
            q1 = module.q(q_params["q1"], batch["obs"], batch["actions"])
            q2 = module.q(q_params["q2"], batch["obs"], batch["actions"])
            return (jnp.mean(jnp.square(q1 - y))
                    + jnp.mean(jnp.square(q2 - y)),
                    0.5 * (jnp.mean(q1) + jnp.mean(q2)))

        def actor_loss_fn(pi_params, params, log_alpha, batch, key):
            p = {**params, "pi": pi_params}
            a, logp = module.sample_action(p, batch["obs"], key)
            q = jnp.minimum(module.q(params["q1"], batch["obs"], a),
                            module.q(params["q2"], batch["obs"], a))
            alpha = jax.lax.stop_gradient(jnp.exp(log_alpha))
            return jnp.mean(alpha * logp - q), jnp.mean(logp)

        def update(params, target_q, log_alpha, opts, batch, key):
            actor_opt, critic_opt, alpha_opt = opts
            k1, k2 = jax.random.split(key)
            # --- critic step
            q_params = {"q1": params["q1"], "q2": params["q2"]}
            (closs, q_mean), cgrads = jax.value_and_grad(
                critic_loss_fn, has_aux=True)(
                    q_params, params, target_q, log_alpha, batch, k1)
            cupd, critic_opt = self._critic_tx.update(cgrads, critic_opt)
            q_params = optax.apply_updates(q_params, cupd)
            params = {**params, **q_params}
            # --- actor step (fresh critics)
            (aloss, logp_mean), agrads = jax.value_and_grad(
                actor_loss_fn, has_aux=True)(
                    params["pi"], params, log_alpha, batch, k2)
            aupd, actor_opt = self._actor_tx.update(agrads, actor_opt)
            params = {**params,
                      "pi": optax.apply_updates(params["pi"], aupd)}
            # --- alpha step (entropy autotune, reference sac_learner)
            alpha_grad = -(jax.lax.stop_gradient(logp_mean)
                           + self._target_entropy)
            alupd, alpha_opt = self._alpha_tx.update(alpha_grad,
                                                     alpha_opt)
            log_alpha = optax.apply_updates(log_alpha, alupd)
            # --- polyak target update
            target_q = jax.tree_util.tree_map(
                lambda t, p: (1 - c.tau) * t + c.tau * p,
                target_q, {"q1": params["q1"], "q2": params["q2"]})
            metrics = {"critic_loss": closs, "actor_loss": aloss,
                       "alpha": jnp.exp(log_alpha), "q_mean": q_mean,
                       "entropy": -logp_mean}
            return (params, target_q, log_alpha,
                    (actor_opt, critic_opt, alpha_opt), metrics)

        return update

    # --------------------------------------------------------------- api
    def train(self) -> Dict[str, Any]:
        import ray_tpu
        c = self.config
        t0 = time.perf_counter()
        weights = jax.device_get(self.params)
        if self._remote:
            ref = ray_tpu.put(weights)
            for r in self._runners:
                r.set_weights.remote(ref)
            batches = ray_tpu.get([
                r.sample.remote(c.rollout_steps_per_iteration)
                for r in self._runners])
        else:
            self._runners[0].set_weights(weights)
            batches = [self._runners[0].sample(
                c.rollout_steps_per_iteration)]
        for b in batches:
            self.buffer.add(b)
            self._total_steps += len(b["rewards"])

        metrics_j: Dict[str, Any] = {}
        if self._total_steps >= c.learning_starts:
            opts = (self._actor_opt, self._critic_opt, self._alpha_opt)
            for _ in range(c.num_updates_per_iteration):
                batch = self.buffer.sample(c.train_batch_size)
                dev = {k: jnp.asarray(v) for k, v in batch.items()
                       if k != "batch_indexes"}
                self._key, sub = jax.random.split(self._key)
                (self.params, self.target_q, self.log_alpha, opts,
                 metrics_j) = self._update_fn(
                     self.params, self.target_q, self.log_alpha, opts,
                     dev, sub)
                self._num_updates += 1
            self._actor_opt, self._critic_opt, self._alpha_opt = opts
        self.iteration += 1
        if self._remote:
            metrics = ray_tpu.get(self._runners[0].get_metrics.remote())
        else:
            metrics = self._runners[0].get_metrics()
        metrics.update({k: float(v) for k, v in metrics_j.items()})
        metrics.update({
            "training_iteration": self.iteration,
            "num_env_steps_sampled_lifetime": self._total_steps,
            "num_updates_lifetime": self._num_updates,
            "buffer_size": len(self.buffer),
            "time_iteration_s": time.perf_counter() - t0,
        })
        return metrics

    def get_state(self) -> Dict[str, Any]:
        return {"params": jax.device_get(self.params),
                "target_q": jax.device_get(self.target_q),
                "log_alpha": float(self.log_alpha),
                "iteration": self.iteration,
                "total_steps": self._total_steps}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.params = jax.device_put(state["params"])
        self.target_q = jax.device_put(state["target_q"])
        self.log_alpha = jnp.asarray(state["log_alpha"], jnp.float32)
        self.iteration = state.get("iteration", 0)
        self._total_steps = state.get("total_steps", 0)

    def stop(self) -> None:
        import ray_tpu
        for r in self._runners:
            try:
                if self._remote:
                    ray_tpu.kill(r)
                else:
                    r.stop()
            except BaseException:
                pass


SACConfig.algo_class = SAC
