"""SAC: soft actor-critic for continuous control.

Reference analog: rllib/algorithms/sac (twin Q critics, tanh-squashed
Gaussian actor, auto-tuned entropy temperature).  Same TPU-first learner
shape as DQN/PPO here: `train_intensity` SGD steps per training_step
compile into ONE jitted lax.scan over presampled replay minibatches —
a single host→device transfer and dispatch per iteration.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import ray_tpu
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.policy import _net_apply, _net_init
from ray_tpu.rllib.replay_buffer import ReplayBuffer
from ray_tpu.rllib.sample_batch import SampleBatch


@dataclasses.dataclass(frozen=True)
class SACSpec:
    obs_dim: int
    action_dim: int
    hidden: Tuple[int, ...] = (128, 128)
    actor_lr: float = 3e-4
    critic_lr: float = 3e-4
    alpha_lr: float = 3e-4
    gamma: float = 0.99
    tau: float = 0.005              # polyak target update rate
    init_alpha: float = 0.2
    #: target entropy; None = -action_dim (the SAC heuristic)
    target_entropy: Optional[float] = None


class SACPolicy:
    """Tanh-squashed Gaussian actor + twin Q critics + auto temperature.

    Actions live in [-1, 1]; callers rescale to env bounds."""

    def __init__(self, spec: SACSpec, seed: int = 0, mesh=None):
        import jax
        import jax.numpy as jnp
        import optax

        self.spec = spec
        self.mesh = mesh
        ka, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
        obs, act = spec.obs_dim, spec.action_dim
        self.params = {
            # actor outputs [mean, log_std] stacked
            "actor": _net_init(ka, (obs, *spec.hidden, 2 * act)),
            "q1": _net_init(k1, (obs + act, *spec.hidden, 1)),
            "q2": _net_init(k2, (obs + act, *spec.hidden, 1)),
            "log_alpha": jnp.asarray(float(np.log(spec.init_alpha))),
        }
        self.target = {
            "q1": jax.tree.map(lambda x: jnp.array(x, copy=True),
                               self.params["q1"]),
            "q2": jax.tree.map(lambda x: jnp.array(x, copy=True),
                               self.params["q2"]),
        }
        # per-group learning rates (actor / critics / temperature)
        self.tx = optax.multi_transform(
            {"actor": optax.adam(spec.actor_lr),
             "critic": optax.adam(spec.critic_lr),
             "alpha": optax.adam(spec.alpha_lr)},
            {"actor": "actor", "q1": "critic", "q2": "critic",
             "log_alpha": "alpha"})
        self.opt_state = self.tx.init(self.params)
        self._rng = jax.random.PRNGKey(seed + 1)
        self._build_fns()

    def get_weights(self):
        import jax

        return jax.tree.map(np.asarray, self.params)

    def set_weights(self, weights) -> None:
        import jax
        import jax.numpy as jnp

        self.params = jax.tree.map(jnp.asarray, weights)

    def _build_fns(self):
        import functools

        import jax
        import jax.numpy as jnp

        spec = self.spec
        act_dim = spec.action_dim
        target_entropy = (spec.target_entropy
                          if spec.target_entropy is not None
                          else -float(act_dim))

        def actor_dist(params, obs):
            out = _net_apply(params["actor"], obs)
            mean, log_std = out[..., :act_dim], out[..., act_dim:]
            log_std = jnp.clip(log_std, -10.0, 2.0)
            return mean, log_std

        def sample_action(params, obs, key):
            mean, log_std = actor_dist(params, obs)
            std = jnp.exp(log_std)
            pre = mean + std * jax.random.normal(key, mean.shape)
            a = jnp.tanh(pre)
            # tanh-squashed Gaussian logp (change of variables)
            logp = jnp.sum(
                -0.5 * jnp.square((pre - mean) / std) - log_std
                - 0.5 * jnp.log(2 * jnp.pi)
                - jnp.log(1 - jnp.square(a) + 1e-6), axis=-1)
            return a, logp

        def q_val(net, obs, act):
            return _net_apply(net, jnp.concatenate([obs, act],
                                                   axis=-1))[..., 0]

        @jax.jit
        def act_fn(params, obs, key, deterministic):
            mean, log_std = actor_dist(params, obs)
            a_det = jnp.tanh(mean)
            a_sto, _ = sample_action(params, obs, key)
            return jnp.where(deterministic, a_det, a_sto)

        def loss_fn(params, target, mini, key):
            k1, k2 = jax.random.split(key)
            alpha = jnp.exp(params["log_alpha"])
            # critic target: r + gamma * (min target Q - alpha logp)
            a2, logp2 = sample_action(params, mini[sb.NEXT_OBS], k1)
            tq = jnp.minimum(
                q_val(target["q1"], mini[sb.NEXT_OBS], a2),
                q_val(target["q2"], mini[sb.NEXT_OBS], a2))
            nonterminal = 1.0 - mini[sb.DONES].astype(jnp.float32)
            backup = jax.lax.stop_gradient(
                mini[sb.REWARDS] + spec.gamma * nonterminal
                * (tq - alpha * logp2))
            q1 = q_val(params["q1"], mini[sb.OBS], mini[sb.ACTIONS])
            q2 = q_val(params["q2"], mini[sb.OBS], mini[sb.ACTIONS])
            critic_loss = jnp.mean(jnp.square(q1 - backup)
                                   + jnp.square(q2 - backup))
            # actor: maximize min-Q of fresh action minus alpha entropy
            a_new, logp_new = sample_action(params, mini[sb.OBS], k2)
            q_new = jnp.minimum(
                q_val(jax.lax.stop_gradient(params["q1"]), mini[sb.OBS],
                      a_new),
                q_val(jax.lax.stop_gradient(params["q2"]), mini[sb.OBS],
                      a_new))
            actor_loss = jnp.mean(
                jax.lax.stop_gradient(alpha) * logp_new - q_new)
            # temperature: drive E[-logp] toward target entropy
            alpha_loss = -jnp.mean(
                params["log_alpha"]
                * jax.lax.stop_gradient(logp_new + target_entropy))
            return critic_loss + actor_loss + alpha_loss, {
                "critic_loss": critic_loss, "actor_loss": actor_loss,
                "alpha": alpha}

        def make_update(the_loss_fn):
            """Build the jitted epoch scan for ANY loss with SAC's
            (params, target, mini, key) signature — loss-wrapping
            learners (CQL's conservative penalty) reuse the whole
            optimizer/polyak machinery instead of copying it."""

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def update(params, opt_state, target, stacked, rng):
                import optax

                def step(carry, mini):
                    params, opt_state, target, rng = carry
                    rng, key = jax.random.split(rng)
                    (loss, stats), grads = jax.value_and_grad(
                        the_loss_fn, has_aux=True)(params, target,
                                                   mini, key)
                    updates, opt_state = self.tx.update(
                        grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                    # polyak target update every SGD step
                    target = jax.tree.map(
                        lambda t, p: t * (1 - spec.tau) + p * spec.tau,
                        target, {"q1": params["q1"],
                                 "q2": params["q2"]})
                    return (params, opt_state, target, rng), stats

                (params, opt_state, target, rng), stats = jax.lax.scan(
                    step, (params, opt_state, target, rng), stacked)
                last = jax.tree.map(lambda s: s[-1], stats)
                return params, opt_state, target, last, rng

            return update

        self._act = act_fn
        #: exposed for loss-wrapping learners (CQL)
        self._loss_fn = loss_fn
        self._sample_action = sample_action
        self._make_update = make_update
        self._update = make_update(loss_fn)

    def compute_actions(self, obs: np.ndarray,
                        deterministic: bool = False) -> np.ndarray:
        import jax

        self._rng, key = jax.random.split(self._rng)
        return np.asarray(self._act(self.params, obs, key,
                                    deterministic))

    def learn_on_minibatches(self, minis: List[SampleBatch]
                             ) -> Dict[str, float]:
        import jax.numpy as jnp

        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            rows = NamedSharding(self.mesh, P(None, "data"))
            repl = NamedSharding(self.mesh, P())
            stacked = {k: jax.device_put(
                np.stack([m[k] for m in minis]), rows)
                for k in minis[0].keys()}
            self.params = jax.device_put(self.params, repl)
            self.opt_state = jax.device_put(self.opt_state, repl)
            self.target = jax.device_put(self.target, repl)
            with jax.set_mesh(self.mesh):
                (self.params, self.opt_state, self.target, stats,
                 self._rng) = self._update(self.params, self.opt_state,
                                           self.target, stacked,
                                           self._rng)
            return {k: float(v) for k, v in stats.items()}
        stacked = {k: jnp.stack([m[k] for m in minis])
                   for k in minis[0].keys()}
        (self.params, self.opt_state, self.target, stats,
         self._rng) = self._update(self.params, self.opt_state,
                                   self.target, stacked, self._rng)
        return {k: float(v) for k, v in stats.items()}


class ContinuousTransitionWorker:
    """CPU actor collecting continuous-action transitions; actions are
    rescaled from the policy's [-1,1] to the env's Box bounds."""

    def __init__(self, *, env: Any, env_config: Optional[Dict] = None,
                 spec: SACSpec, num_envs: int = 1,
                 rollout_fragment_length: int = 50, seed: int = 0,
                 policy_cls=None):
        import os

        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from ray_tpu.rllib.rollout_worker import _make_env

        if num_envs != 1:
            raise ValueError(
                "ContinuousTransitionWorker steps one env per actor; "
                "scale with num_workers instead of num_envs_per_worker")
        self.env = _make_env(env, env_config)
        # any continuous policy with the SACPolicy surface drives this
        # worker (TD3Policy reuses it)
        self.policy = (policy_cls or SACPolicy)(spec, seed=seed)
        self.fragment = rollout_fragment_length
        space = getattr(self.env, "action_space", None)
        self._low = np.asarray(getattr(space, "low", -1.0))
        self._high = np.asarray(getattr(space, "high", 1.0))
        self._shape = tuple(getattr(space, "shape", (spec.action_dim,)))
        self._obs = self.env.reset(seed=seed)[0]
        self._ep_reward = 0.0
        self.episode_returns: List[float] = []

    def set_weights(self, weights) -> None:
        self.policy.set_weights(weights)

    def _rescale(self, a: np.ndarray) -> np.ndarray:
        return self._low + (a + 1.0) * 0.5 * (self._high - self._low)

    def sample(self) -> SampleBatch:
        T = self.fragment
        spec = self.policy.spec
        obs_buf = np.zeros((T,) + np.shape(self._obs), np.float32)
        next_buf = np.zeros_like(obs_buf)
        act_buf = np.zeros((T, spec.action_dim), np.float32)
        rew_buf = np.zeros((T,), np.float32)
        done_buf = np.zeros((T,), np.bool_)
        for t in range(T):
            obs = np.asarray(self._obs, np.float32)
            a = self.policy.compute_actions(obs[None])[0]
            env_a = self._rescale(a).reshape(self._shape)
            o2, r, term, trunc, _ = self.env.step(env_a)
            obs_buf[t] = obs
            act_buf[t] = a          # the buffer keeps [-1,1] actions
            rew_buf[t] = r
            done_buf[t] = term      # truncation is not terminal
            next_buf[t] = np.asarray(o2, np.float32)
            self._ep_reward += float(r)
            if term or trunc:
                self.episode_returns.append(self._ep_reward)
                self._ep_reward = 0.0
                o2 = self.env.reset()[0]
            self._obs = o2
        return SampleBatch({sb.OBS: obs_buf, sb.ACTIONS: act_buf,
                            sb.REWARDS: rew_buf, sb.DONES: done_buf,
                            sb.NEXT_OBS: next_buf})

    def pop_episode_returns(self) -> List[float]:
        out = self.episode_returns
        self.episode_returns = []
        return out


@dataclasses.dataclass
class SACConfig(AlgorithmConfig):
    hidden: Tuple[int, ...] = (128, 128)
    buffer_size: int = 100_000
    learning_starts: int = 500
    train_batch_size: int = 128     # replay minibatch rows per SGD step
    train_intensity: int = 16       # SGD steps per training_step
    tau: float = 0.005
    init_alpha: float = 0.2
    target_entropy: Optional[float] = None
    rollout_fragment_length: int = 50
    obs_dim: Optional[int] = None
    action_dim: Optional[int] = None
    #: >1: the SAC update runs data-parallel over this many local devices
    learner_devices: int = 1

    def sac_spec(self) -> SACSpec:
        return SACSpec(obs_dim=self.obs_dim, action_dim=self.action_dim,
                       hidden=tuple(self.hidden), actor_lr=self.lr,
                       critic_lr=self.lr, gamma=self.gamma,
                       tau=self.tau, init_alpha=self.init_alpha,
                       target_entropy=self.target_entropy)


class ContinuousOffPolicy(Algorithm):
    """Shared driver for continuous off-policy learners (SAC / TD3 /
    DDPG): probe Box spaces, gang up transition workers, and per
    training_step sample → replay-add → one jitted update burst →
    weight broadcast.  Subclasses set ``_policy_cls`` and
    ``_make_spec``; ``_mesh`` optionally supplies a learner mesh."""

    _policy_cls = None

    def _make_spec(self, config):
        raise NotImplementedError

    def _mesh(self, config):
        return None

    def setup(self, config) -> None:
        if config.obs_dim is None or config.action_dim is None:
            from ray_tpu.rllib.rollout_worker import _make_env

            env = _make_env(config.env, config.env_config)
            try:
                config.obs_dim = int(
                    np.prod(env.observation_space.shape))
                space = env.action_space
                if hasattr(space, "n") or not getattr(space, "shape",
                                                      None):
                    raise TypeError(
                        f"{type(self).__name__} supports continuous "
                        "(Box) action spaces only; use DQN/PPO for "
                        "discrete envs")
                config.action_dim = int(np.prod(space.shape))
            finally:
                env.close() if hasattr(env, "close") else None
        spec = self._make_spec(config)
        self.policy = self._policy_cls(spec, seed=config.seed,
                                       mesh=self._mesh(config))
        self.buffer = ReplayBuffer(config.buffer_size, seed=config.seed)
        remote_cls = ray_tpu.remote(
            num_cpus=config.num_cpus_per_worker)(
                ContinuousTransitionWorker)
        self.workers = [
            remote_cls.remote(
                env=config.env, env_config=config.env_config,
                spec=self._worker_spec(config, i),
                num_envs=config.num_envs_per_worker,
                rollout_fragment_length=config.rollout_fragment_length,
                seed=config.seed + 1000 * (i + 1),
                policy_cls=self._policy_cls)
            for i in range(config.num_workers)]

    def _worker_spec(self, config, i: int):
        """Spec for worker i — hook for per-worker exploration
        (ApexDDPG's sigma ladder)."""
        return self._make_spec(config)

    def training_step(self) -> Dict[str, Any]:
        c = self.config
        parts = ray_tpu.get([w.sample.remote() for w in self.workers],
                            timeout=300.0)
        for p in parts:
            self.buffer.add(p)
        stats: Dict[str, Any] = {
            "buffer_size": len(self.buffer),
            "timesteps_this_iter": sum(p.count for p in parts)}
        if len(self.buffer) >= max(c.learning_starts,
                                   c.train_batch_size):
            stats.update(self._replay_update())
            weights = self.policy.get_weights()
            ref = ray_tpu.put(weights)
            ray_tpu.get([w.set_weights.remote(ref)
                         for w in self.workers], timeout=60.0)
        returns = ray_tpu.get(
            [w.pop_episode_returns.remote() for w in self.workers],
            timeout=60.0)
        self._episode_returns.extend(r for p in returns for r in p)
        return stats

    def _replay_update(self) -> Dict[str, Any]:
        """One learner burst off the replay buffer (train_intensity
        jitted SGD steps) — shared by the sync driver and the async
        Ape-X variant."""
        c = self.config
        minis = [self.buffer.sample(c.train_batch_size)
                 for _ in range(c.train_intensity)]
        return self.policy.learn_on_minibatches(minis)

    def cleanup(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        self.workers = []


class SAC(ContinuousOffPolicy):
    _config_cls = SACConfig
    _policy_cls = SACPolicy

    def _make_spec(self, config: SACConfig) -> SACSpec:
        return config.sac_spec()

    def _mesh(self, config: SACConfig):
        if config.learner_devices > 1 and \
                config.train_batch_size % config.learner_devices:
            raise ValueError(
                f"train_batch_size={config.train_batch_size} must divide "
                f"by learner_devices={config.learner_devices}")
        from ray_tpu.rllib.algorithm import learner_mesh

        return learner_mesh(config.learner_devices)
