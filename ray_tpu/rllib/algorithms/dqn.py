"""DQN: replay-buffer off-policy Q-learning (double-DQN update).

Parity: reference rllib/algorithms/dqn (new-stack DQN with
prioritized replay, target network, double-Q) — sized to this stack:
one SINGLE-JIT update (double-DQN TD loss + adam + importance weights),
epsilon-greedy env runners on a linear schedule, target-network sync
every `target_network_update_freq` updates, uniform or prioritized
buffer from rllib.utils.replay_buffers.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib.algorithm_config import AlgorithmConfig
from ray_tpu.rllib.utils.replay_buffers import (PrioritizedReplayBuffer,
                                                ReplayBuffer)
from ray_tpu.rllib.utils.schedules import LinearSchedule


# ------------------------------------------------------------ q module
def _fnoise(x):
    """Factorized-noise squash f(x) = sign(x)·sqrt(|x|) (NoisyNet)."""
    return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


@dataclasses.dataclass(frozen=True)
class QModule:
    """MLP Q-network: obs -> Q(s, ·) or a return DISTRIBUTION.

    Rainbow components (reference rllib/algorithms/dqn — dueling heads,
    distributional C51, noisy nets):
    - dueling: torso feeds separate value/advantage heads combined as
      V + A - mean(A) (per-atom in distributional mode).
    - num_atoms > 1: C51 — heads emit logits over a fixed support
      linspace(v_min, v_max, num_atoms); Q(s,a) = E_p[z].
    - noisy: head layers carry factorized-Gaussian parameter noise
      (w = mu + sigma·f(eps_out)⊗f(eps_in)); sampling a fresh eps per
      forward IS the exploration, replacing epsilon-greedy."""

    obs_dim: int
    num_actions: int
    hidden: Sequence[int] = (64, 64)
    dueling: bool = False
    num_atoms: int = 1
    v_min: float = -10.0
    v_max: float = 10.0
    noisy: bool = False
    sigma0: float = 0.5

    @property
    def support(self) -> jax.Array:
        return jnp.linspace(self.v_min, self.v_max, self.num_atoms)

    def _dense(self, key, din, dout, scale, head: bool = False):
        w = jax.random.orthogonal(key, max(din, dout))[:din, :dout]
        layer = {"w": (w * scale).astype(jnp.float32),
                 "b": jnp.zeros((dout,), jnp.float32)}
        if head and self.noisy:
            s = self.sigma0 / np.sqrt(din)
            layer["w_sig"] = jnp.full((din, dout), s, jnp.float32)
            layer["b_sig"] = jnp.full((dout,), s, jnp.float32)
        return layer

    def init(self, key: jax.Array) -> dict:
        keys = jax.random.split(key, len(self.hidden) + 3)
        ki = iter(keys)
        layers = []
        din = self.obs_dim
        for h in self.hidden:
            layers.append(self._dense(next(ki), din, h, jnp.sqrt(2.0)))
            din = h
        K = self.num_atoms
        if self.dueling:
            return {"q": layers,
                    "adv": [self._dense(next(ki), din,
                                        self.num_actions * K, 0.01,
                                        head=True)],
                    "val": [self._dense(next(ki), din, K, 1.0,
                                        head=True)]}
        layers.append(self._dense(next(ki), din,
                                  self.num_actions * K, 0.01, head=True))
        return {"q": layers}

    @staticmethod
    def _apply(layer: dict, x, key):
        """One dense layer; with noise params AND a key, apply
        factorized-Gaussian parameter noise (mu-only when key is None —
        the deterministic/eval path)."""
        w, b = layer["w"], layer["b"]
        if "w_sig" in layer and key is not None:
            k_in, k_out = jax.random.split(key)
            e_in = _fnoise(jax.random.normal(k_in, (w.shape[0],)))
            e_out = _fnoise(jax.random.normal(k_out, (w.shape[1],)))
            w = w + layer["w_sig"] * (e_in[:, None] * e_out[None, :])
            b = b + layer["b_sig"] * e_out
        return x @ w + b

    def _head_out(self, params: dict, obs, key):
        """Raw head output: (B, A) for scalar Q, (B, A, K) logits for
        distributional."""
        x = obs
        torso = params["q"] if self.dueling else params["q"][:-1]
        for layer in torso:
            x = jnp.tanh(x @ layer["w"] + layer["b"])
        A, K = self.num_actions, self.num_atoms
        if self.dueling:
            ka, kv = ((None, None) if key is None
                      else jax.random.split(key))
            a = self._apply(params["adv"][0], x, ka)
            v = self._apply(params["val"][0], x, kv)
            if K == 1:
                return v + a - jnp.mean(a, axis=-1, keepdims=True)
            a = a.reshape(a.shape[0], A, K)
            v = v.reshape(v.shape[0], 1, K)
            return v + a - jnp.mean(a, axis=1, keepdims=True)
        out = self._apply(params["q"][-1], x, key)
        return out if K == 1 else out.reshape(out.shape[0], A, K)

    def forward_dist(self, params: dict, obs, key=None) -> jax.Array:
        """(B, A, K) return-distribution logits (num_atoms > 1 only)."""
        return self._head_out(params, obs, key)

    def forward(self, params: dict, obs, key=None) -> jax.Array:
        """(B, A) Q-values (expectation over the support in C51)."""
        out = self._head_out(params, obs, key)
        if self.num_atoms == 1:
            return out
        return jnp.sum(jax.nn.softmax(out, axis=-1) * self.support,
                       axis=-1)

    def forward_np(self, params_np: dict, obs,
                   rng: Optional[np.random.Generator] = None
                   ) -> np.ndarray:
        """Numpy action-value path for env runners; `rng` samples the
        NoisyNet exploration noise."""
        x = obs
        torso = (params_np["q"] if self.dueling
                 else params_np["q"][:-1])
        for layer in torso:
            x = np.tanh(x @ layer["w"] + layer["b"])

        def apply(layer, x):
            w, b = layer["w"], layer["b"]
            if "w_sig" in layer and rng is not None:
                e_in = rng.standard_normal(w.shape[0])
                e_out = rng.standard_normal(w.shape[1])
                f = lambda v: np.sign(v) * np.sqrt(np.abs(v))
                e_in, e_out = f(e_in), f(e_out)
                w = w + layer["w_sig"] * (e_in[:, None] * e_out[None, :])
                b = b + layer["b_sig"] * e_out
            return x @ w + b

        A, K = self.num_actions, self.num_atoms
        if self.dueling:
            a = apply(params_np["adv"][0], x)
            v = apply(params_np["val"][0], x)
            if K == 1:
                return v + a - a.mean(axis=-1, keepdims=True)
            a = a.reshape(len(a), A, K)
            v = v.reshape(len(v), 1, K)
            logits = v + a - a.mean(axis=1, keepdims=True)
        else:
            out = apply(params_np["q"][-1], x)
            if K == 1:
                return out
            logits = out.reshape(len(out), A, K)
        z = logits - logits.max(axis=-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=-1, keepdims=True)
        support = np.linspace(self.v_min, self.v_max, K)
        return (p * support).sum(axis=-1)


class QEnvRunner:
    """Epsilon-greedy vectorized sampler emitting FLAT transitions
    (s, a, r, s', done) — the off-policy contract, unlike the
    time-major on-policy runner."""

    def __init__(self, config: "DQNConfig", worker_index: int = 0):
        import gymnasium as gym
        self.config = config
        seed = config.seed + 1000 * worker_index
        self._envs = gym.make_vec(config.env,
                                  num_envs=config.num_envs_per_env_runner,
                                  vectorization_mode="sync")
        space = self._envs.single_action_space
        if not hasattr(space, "n"):
            raise ValueError("DQN needs a discrete action space")
        self.module = QModule(
            int(np.prod(self._envs.single_observation_space.shape)),
            int(space.n), tuple(config.hidden),
            dueling=config.dueling, num_atoms=config.num_atoms,
            v_min=config.v_min, v_max=config.v_max,
            noisy=config.noisy, sigma0=config.noisy_sigma0)
        # n-step returns: per-env pending transition windows (reference
        # rainbow n_step; horizon shortens at episode end)
        self._nstep = max(1, int(config.n_step))
        self._pending = [[] for _ in
                         range(config.num_envs_per_env_runner)]
        self.params = jax.tree_util.tree_map(
            np.asarray, self.module.init(jax.random.PRNGKey(seed)))
        self._rng = np.random.default_rng(seed + 1)
        self._obs, _ = self._envs.reset(seed=seed)
        self._prev_done = np.zeros(config.num_envs_per_env_runner, bool)
        self._eps = LinearSchedule(config.epsilon_timesteps,
                                   config.final_epsilon,
                                   config.initial_epsilon)
        self._steps = 0
        self._ep_ret = np.zeros(config.num_envs_per_env_runner)
        self._recent: list = []

    def ping(self):
        return "pong"

    def set_weights(self, weights) -> None:
        self.params = jax.tree_util.tree_map(np.asarray, weights)

    def _emit_nstep(self, rows, env_i: int, flush: bool) -> None:
        """Pop matured windows: (s0, a0, sum gamma^k r_k, s_h, term_h,
        horizon h). On flush (episode boundary) every remaining entry
        emits with its shortened horizon."""
        g = self.config.gamma
        buf = self._pending[env_i]
        while buf and (flush or len(buf) >= self._nstep):
            horizon = min(len(buf), self._nstep)
            R = 0.0
            for k in range(horizon):
                R += (g ** k) * buf[k][2]
            o0, a0 = buf[0][0], buf[0][1]
            nobs_h, term_h = buf[horizon - 1][3], buf[horizon - 1][4]
            rows["obs"].append(o0)
            rows["actions"].append(a0)
            rows["rewards"].append(np.float32(R))
            rows["new_obs"].append(nobs_h)
            rows["terminateds"].append(np.float32(term_h))
            rows["nsteps"].append(np.float32(horizon))
            buf.pop(0)

    def sample(self, num_steps: int) -> Dict[str, np.ndarray]:
        rows = {k: [] for k in ("obs", "actions", "rewards", "new_obs",
                                "terminateds", "nsteps")}
        N = self.config.num_envs_per_env_runner
        for _ in range(num_steps):
            if self.config.noisy:
                # NoisyNet: a fresh parameter-noise sample per step IS
                # the exploration — no epsilon
                q = self.module.forward_np(
                    self.params, self._obs.astype(np.float32),
                    rng=self._rng)
                action = q.argmax(-1).astype(np.int32)
            else:
                q = self.module.forward_np(self.params,
                                           self._obs.astype(np.float32))
                greedy = q.argmax(-1)
                explore = (self._rng.random(N)
                           < self._eps(self._steps))
                random_a = self._rng.integers(0, q.shape[-1], N)
                action = np.where(explore, random_a,
                                  greedy).astype(np.int32)
            nobs, reward, term, trunc, _ = self._envs.step(action)
            done = term | trunc
            valid = ~self._prev_done     # autoreset filler: drop
            for i in np.nonzero(valid)[0]:
                self._pending[i].append(
                    (self._obs[i].astype(np.float32),
                     np.int32(action[i]), float(reward[i]),
                     nobs[i].astype(np.float32), bool(term[i])))
                self._emit_nstep(rows, i, flush=bool(done[i]))
            self._ep_ret[valid] += reward[valid]
            for i in np.nonzero(done & valid)[0]:
                self._recent.append(float(self._ep_ret[i]))
                self._ep_ret[i] = 0.0
            self._recent = self._recent[-100:]
            self._prev_done = done
            self._obs = nobs
            self._steps += N
        if not rows["rewards"]:
            obs_shape = self._obs.shape[1:]
            return {"obs": np.empty((0,) + obs_shape, np.float32),
                    "actions": np.empty((0,), np.int32),
                    "rewards": np.empty((0,), np.float32),
                    "new_obs": np.empty((0,) + obs_shape, np.float32),
                    "terminateds": np.empty((0,), np.float32),
                    "nsteps": np.empty((0,), np.float32)}
        return {k: np.stack(v) for k, v in rows.items()}

    def get_metrics(self) -> Dict[str, Any]:
        return {"episode_return_mean": (float(np.mean(self._recent))
                                        if self._recent else float("nan")),
                "num_episodes": len(self._recent),
                "epsilon": self._eps(self._steps),
                "num_env_steps_sampled": self._steps}

    def stop(self) -> None:
        self._envs.close()


@dataclasses.dataclass
class DQNConfig(AlgorithmConfig):
    env: str = "CartPole-v1"
    num_env_runners: int = 0              # 0 = local
    num_envs_per_env_runner: int = 8
    rollout_steps_per_iteration: int = 64
    hidden: Sequence[int] = (64, 64)
    lr: float = 5e-4
    gamma: float = 0.99
    buffer_size: int = 50_000
    prioritized_replay: bool = True
    train_batch_size: int = 64
    num_updates_per_iteration: int = 16
    learning_starts: int = 500            # env steps before updates
    target_network_update_freq: int = 100  # in updates
    dueling: bool = False                  # V + A - mean(A) heads
    n_step: int = 1                        # multi-step TD returns
    # rainbow: distributional C51 (num_atoms > 1) + noisy nets
    num_atoms: int = 1
    v_min: float = -10.0
    v_max: float = 10.0
    noisy: bool = False                    # NoisyNet exploration
    noisy_sigma0: float = 0.5
    initial_epsilon: float = 1.0
    final_epsilon: float = 0.02
    epsilon_timesteps: int = 10_000
    double_q: bool = True
    seed: int = 0

class DQN:
    """Iterative trainer: sample -> buffer -> k double-DQN updates."""

    def __init__(self, config: DQNConfig):
        self.config = config
        c = config
        if c.num_env_runners == 0:
            self._runners = [QEnvRunner(c)]
            self._remote = False
        else:
            import ray_tpu
            cls = ray_tpu.remote(num_cpus=1)(QEnvRunner)
            self._runners = [cls.remote(c, worker_index=i + 1)
                             for i in range(c.num_env_runners)]
            self._remote = True
        self.module = (self._runners[0].module if not self._remote
                       else QModule(*self._probe_dims(), tuple(c.hidden),
                                    dueling=c.dueling,
                                    num_atoms=c.num_atoms, v_min=c.v_min,
                                    v_max=c.v_max, noisy=c.noisy,
                                    sigma0=c.noisy_sigma0))
        self.params = self.module.init(jax.random.PRNGKey(c.seed))
        self.target_params = jax.tree_util.tree_map(jnp.copy, self.params)
        self._tx = optax.adam(c.lr)
        self.opt_state = self._tx.init(self.params)
        self.buffer = (PrioritizedReplayBuffer(c.buffer_size,
                                               seed=c.seed)
                       if c.prioritized_replay
                       else ReplayBuffer(c.buffer_size, seed=c.seed))
        self._update_fn = jax.jit(self._build_update())
        self._noise_key = jax.random.PRNGKey(c.seed + 17)
        self._num_updates = 0
        self._total_steps = 0
        self.iteration = 0

    def _probe_dims(self) -> Tuple[int, int]:
        import gymnasium as gym
        env = gym.make(self.config.env)
        dims = (int(np.prod(env.observation_space.shape)),
                int(env.action_space.n))
        env.close()
        return dims

    def _build_update(self):
        c = self.config
        module = self.module

        def g_eff_of(batch):
            # n-step bootstrap: reward already sums gamma^k r_k over
            # the window; discount the tail by gamma^horizon
            return c.gamma ** batch.get(
                "nsteps", jnp.ones_like(batch["rewards"]))

        def loss_scalar(params, target_params, batch, key):
            k1, k2, k3 = jax.random.split(key, 3)
            q = module.forward(params, batch["obs"], k1)
            q_sa = jnp.take_along_axis(
                q, batch["actions"][:, None].astype(jnp.int32),
                axis=-1)[:, 0]
            q_next_target = module.forward(target_params,
                                           batch["new_obs"], k2)
            if c.double_q:
                # k3, not k2: online action selection must not share the
                # target net's noise realization (correlated parameter
                # noise would re-couple selection and evaluation)
                a_star = jnp.argmax(
                    module.forward(params, batch["new_obs"], k3), axis=-1)
                q_next = jnp.take_along_axis(
                    q_next_target, a_star[:, None], axis=-1)[:, 0]
            else:
                q_next = jnp.max(q_next_target, axis=-1)
            target = (batch["rewards"]
                      + g_eff_of(batch) * (1.0 - batch["terminateds"])
                      * jax.lax.stop_gradient(q_next))
            td = q_sa - target
            w = batch.get("weights", jnp.ones_like(td))
            return jnp.mean(w * jnp.square(td)), jnp.abs(td)

        def loss_c51(params, target_params, batch, key):
            """Distributional C51 (reference rainbow): project the
            Bellman-updated target distribution onto the fixed support
            and minimise cross-entropy. The per-sample cross-entropy
            doubles as the priority signal."""
            K = c.num_atoms
            z = module.support                      # (K,)
            dz = (c.v_max - c.v_min) / (K - 1)
            k1, k2, k3 = jax.random.split(key, 3)
            logits = module.forward_dist(params, batch["obs"], k1)
            logp_sa = jax.nn.log_softmax(jnp.take_along_axis(
                logits, batch["actions"][:, None, None].astype(
                    jnp.int32).repeat(K, axis=2), axis=1)[:, 0],
                axis=-1)                            # (B, K)
            t_logits = module.forward_dist(target_params,
                                           batch["new_obs"], k2)
            p_next = jax.nn.softmax(t_logits, axis=-1)   # (B, A, K)
            if c.double_q:
                q_online = module.forward(params, batch["new_obs"], k3)
                a_star = jnp.argmax(q_online, axis=-1)
            else:
                a_star = jnp.argmax(jnp.sum(p_next * z, -1), axis=-1)
            p_a = jnp.take_along_axis(
                p_next, a_star[:, None, None].repeat(K, axis=2),
                axis=1)[:, 0]                       # (B, K)
            Tz = jnp.clip(
                batch["rewards"][:, None]
                + g_eff_of(batch)[:, None]
                * (1.0 - batch["terminateds"])[:, None] * z[None, :],
                c.v_min, c.v_max)                   # (B, K)
            b = (Tz - c.v_min) / dz
            lo = jnp.clip(jnp.floor(b), 0, K - 1)
            hi = jnp.clip(lo + 1, 0, K - 1)
            # when b lands exactly on an atom (lo == hi at the top
            # edge), give it full mass instead of losing it
            w_lo = (hi - b) + (lo == hi)
            w_hi = b - lo
            onehot_lo = jax.nn.one_hot(lo.astype(jnp.int32), K)
            onehot_hi = jax.nn.one_hot(hi.astype(jnp.int32), K)
            m = jnp.sum(
                p_a[:, :, None] * (w_lo[:, :, None] * onehot_lo
                                   + w_hi[:, :, None] * onehot_hi),
                axis=1)                             # (B, K)
            m = jax.lax.stop_gradient(m)
            xent = -jnp.sum(m * logp_sa, axis=-1)   # (B,)
            w = batch.get("weights", jnp.ones_like(xent))
            return jnp.mean(w * xent), xent

        loss_fn = loss_c51 if c.num_atoms > 1 else loss_scalar

        def update(params, target_params, opt_state, batch, key):
            (loss, td), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, target_params, batch,
                                       key)
            updates, opt_state = self._tx.update(grads, opt_state)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, td

        return update

    # ------------------------------------------------------------- api
    def train(self) -> Dict[str, Any]:
        import ray_tpu
        c = self.config
        t0 = time.perf_counter()
        weights = jax.device_get(self.params)
        if self._remote:
            ref = ray_tpu.put(weights)
            # weights FIRST (actor-call ordering applies them before the
            # sample), matching the local path's semantics
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
            if len(b["rewards"]):
                self.buffer.add(b)
                self._total_steps += len(b["rewards"])

        loss = float("nan")
        if self._total_steps >= c.learning_starts:
            for _ in range(c.num_updates_per_iteration):
                batch = self.buffer.sample(c.train_batch_size)
                dev = {k: jnp.asarray(v) for k, v in batch.items()
                       if k != "batch_indexes"}
                self._noise_key, sub = jax.random.split(self._noise_key)
                self.params, self.opt_state, loss_j, td = \
                    self._update_fn(self.params, self.target_params,
                                    self.opt_state, dev, sub)
                loss = float(loss_j)
                self._num_updates += 1
                if isinstance(self.buffer, PrioritizedReplayBuffer):
                    self.buffer.update_priorities(
                        batch["batch_indexes"], np.asarray(td))
                if self._num_updates % c.target_network_update_freq == 0:
                    self.target_params = jax.tree_util.tree_map(
                        jnp.copy, self.params)
        self.iteration += 1
        if self._remote:
            metrics = ray_tpu.get(
                self._runners[0].get_metrics.remote())
        else:
            metrics = self._runners[0].get_metrics()
        metrics.update({
            "training_iteration": self.iteration,
            "num_env_steps_sampled_lifetime": self._total_steps,
            "num_updates_lifetime": self._num_updates,
            "td_loss": loss,
            "buffer_size": len(self.buffer),
            "time_iteration_s": time.perf_counter() - t0,
        })
        return metrics

    def get_state(self) -> Dict[str, Any]:
        """Checkpointable trainer state (replay buffer contents stay
        local — the reference's DQN checkpoints exclude them too by
        default)."""
        return {"params": jax.device_get(self.params),
                "target_params": jax.device_get(self.target_params),
                "opt_state": jax.device_get(self.opt_state),
                "num_updates": self._num_updates,
                "total_steps": self._total_steps,
                "iteration": self.iteration}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.params = jax.device_put(state["params"])
        self.target_params = jax.device_put(state["target_params"])
        self.opt_state = jax.device_put(state["opt_state"])
        self._num_updates = state.get("num_updates", 0)
        self._total_steps = state.get("total_steps", 0)
        self.iteration = state.get("iteration", 0)

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


DQNConfig.algo_class = DQN
