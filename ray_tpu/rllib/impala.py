"""IMPALA: asynchronous off-policy actor-critic with v-trace.

Reference analog: ``rllib/algorithms/impala/impala.py:610-646``
(training_step pulling async sample refs) + ``rllib/execution/
multi_gpu_learner_thread.py:20-46`` (loader threads staging host batches
into per-GPU buffers while the learner consumes).

TPU-first redesign of the learner pipeline: instead of loader threads
and tower buffers, the learner exploits XLA's async dispatch as the
double buffer — each ready rollout is ``jax.device_put`` (async H2D)
while the PREVIOUS batch's jitted update is still executing on the chip,
and the update call for the staged batch is dispatched before its
result is fetched.  One host sync per training_step.  Rollout workers
run continuously with bounded in-flight sample requests and receive
weight broadcasts every ``broadcast_interval`` learner steps (stale-but-
bounded off-policyness — exactly what v-trace corrects).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.rllib.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.policy import PolicySpec, _net_apply, _net_init


def vtrace(behaviour_logp, target_logp, rewards, dones, values,
           bootstrap_value, *, gamma: float = 0.99, rho_clip: float = 1.0,
           c_clip: float = 1.0):
    """V-trace targets and policy-gradient advantages (IMPALA eq. 1).

    All inputs time-major (T, B); values are the TARGET network's
    V(x_t); bootstrap_value is V(x_T).  Returns (vs, pg_advantages),
    both (T, B), gradient-stopped.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    rho = jnp.minimum(rho_clip, jnp.exp(target_logp - behaviour_logp))
    c = jnp.minimum(c_clip, rho)
    nonterminal = 1.0 - dones.astype(jnp.float32)
    # V(x_{t+1}) with terminal cut: 0 after done (the reward already
    # carries any truncation bootstrap folded in by the worker).
    values_tp1 = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0) * nonterminal
    deltas = rho * (rewards + gamma * values_tp1 - values)

    def back(acc, xs):
        delta_t, c_t, nt_t = xs
        acc = delta_t + gamma * c_t * nt_t * acc
        return acc, acc

    _, dvs = lax.scan(back, jnp.zeros_like(bootstrap_value),
                      (deltas, c, nonterminal), reverse=True)
    vs = values + dvs
    vs_tp1 = jnp.concatenate(
        [vs[1:], bootstrap_value[None]], axis=0) * nonterminal
    pg_adv = rho * (rewards + gamma * vs_tp1 - values)
    return lax.stop_gradient(vs), lax.stop_gradient(pg_adv)


@dataclasses.dataclass
class IMPALAConfig(AlgorithmConfig):
    vf_coeff: float = 0.5
    entropy_coeff: float = 0.01
    grad_clip: float = 40.0
    rho_clip: float = 1.0
    c_clip: float = 1.0
    hidden: Tuple[int, ...] = (64, 64)
    #: learner steps between weight broadcasts to the rollout workers.
    broadcast_interval: int = 1
    #: bounded sample-request pipeline per worker (reference:
    #: max_sample_requests_in_flight_per_worker).
    max_requests_in_flight_per_worker: int = 2
    obs_dim: Optional[int] = None
    n_actions: Optional[int] = None
    #: >1: the v-trace update runs data-parallel over this many local
    #: devices (fragment batch sharded on B, grads psum'd by GSPMD)
    learner_devices: int = 1


class IMPALAPolicy:
    """Actor-critic policy with the v-trace actor-critic update as ONE
    jitted call over a time-major fragment batch."""

    def __init__(self, cfg: IMPALAConfig, seed: int = 0, mesh=None):
        import jax
        import optax

        self.cfg = cfg
        self.mesh = mesh
        from ray_tpu.rllib.models import Encoder, ModelConfig

        kp, kv, kh1, kh2 = jax.random.split(jax.random.PRNGKey(seed), 4)
        # JaxPolicy's feedforward tower layout (enc + linear head) via
        # the SAME Encoder the rollout workers build, so the learner's
        # weight broadcast can never structurally drift from them
        self._encoder = Encoder(
            (cfg.obs_dim,), ModelConfig(fcnet_hiddens=tuple(cfg.hidden)))
        feat = self._encoder.feature_dim
        self.params = {
            "pi": {"enc": self._encoder.init(kp),
                   "head": _net_init(kh1, (feat, cfg.n_actions))},
            "vf": {"enc": self._encoder.init(kv),
                   "head": _net_init(kh2, (feat, 1))},
        }
        self.tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip),
                              optax.adam(cfg.lr))
        self.opt_state = self.tx.init(self.params)
        self._build()

    def _policy_loss(self, target_logp, behaviour_logp, pg_adv):
        """Vanilla IMPALA policy gradient on v-trace advantages;
        APPO overrides with the clipped PPO surrogate."""
        import jax.numpy as jnp

        return -jnp.mean(target_logp * pg_adv)

    def _build(self):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg

        def loss_fn(params, batch):
            T, B = batch["actions"].shape
            obs = batch["obs"]                      # (T, B, D)
            enc = self._encoder

            def tower(p, x):
                # encoder applies over the last dim; flatten (T, B) rows
                lead = x.shape[:-1]
                feats = enc.apply(p["enc"], x.reshape(-1, x.shape[-1]))
                return _net_apply(p["head"],
                                  feats.reshape(*lead, -1))

            logits = tower(params["pi"], obs)       # (T, B, A)
            values = tower(params["vf"], obs)[..., 0]
            bootstrap = tower(params["vf"], batch["last_obs"])[..., 0]
            logp_all = jax.nn.log_softmax(logits)
            target_logp = jnp.take_along_axis(
                logp_all, batch["actions"][..., None].astype(jnp.int32),
                axis=-1)[..., 0]
            vs, pg_adv = vtrace(
                batch["behaviour_logp"], target_logp, batch["rewards"],
                batch["dones"], values, bootstrap, gamma=cfg.gamma,
                rho_clip=cfg.rho_clip, c_clip=cfg.c_clip)
            pi_loss = self._policy_loss(target_logp,
                                        batch["behaviour_logp"], pg_adv)
            vf_loss = 0.5 * jnp.mean(jnp.square(vs - values))
            entropy = -jnp.mean(
                jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
            total = pi_loss + cfg.vf_coeff * vf_loss \
                - cfg.entropy_coeff * entropy
            return total, {"policy_loss": pi_loss, "vf_loss": vf_loss,
                           "entropy": entropy, "total_loss": total}

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, batch):
            import optax

            (_, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, stats

        self._update = update

    def stage(self, host_batch: Dict[str, np.ndarray]):
        """Async host→device transfer (the loader-thread replacement).
        With a learner mesh, arrays land already sharded on the batch
        axis (time-major fragments: (T,B,...) shard on axis 1; last_obs
        (B,D) on axis 0)."""
        import jax

        if self.mesh is None:
            return jax.tree.map(jax.device_put, host_batch)
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = {}
        for k, v in host_batch.items():
            spec = P("data") if k == "last_obs" else P(None, "data")
            out[k] = jax.device_put(v, NamedSharding(self.mesh, spec))
        return out

    def learn_staged(self, dev_batch) -> Dict[str, Any]:
        """Dispatch the update; returns DEVICE stats (not synced — the
        caller fetches once per training_step)."""
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            self.params = jax.device_put(self.params, repl)
            self.opt_state = jax.device_put(self.opt_state, repl)
            with jax.set_mesh(self.mesh):
                self.params, self.opt_state, stats = self._update(
                    self.params, self.opt_state, dev_batch)
            return stats
        self.params, self.opt_state, stats = self._update(
            self.params, self.opt_state, dev_batch)
        return stats

    def get_weights(self):
        import jax

        return jax.tree.map(np.asarray, self.params)


class IMPALA(Algorithm):
    _config_cls = IMPALAConfig
    _policy_cls = IMPALAPolicy

    def setup(self, config: IMPALAConfig) -> None:
        import ray_tpu
        from ray_tpu.rllib.ppo import _introspect_spaces
        from ray_tpu.rllib.rollout_worker import TrajectoryWorker

        _introspect_spaces(config)
        if config.learner_devices > 1 and \
                config.num_envs_per_worker % config.learner_devices:
            raise ValueError(
                f"num_envs_per_worker={config.num_envs_per_worker} must "
                f"divide by learner_devices={config.learner_devices} "
                f"(the fragment batch axis shards across the mesh)")
        from ray_tpu.rllib.algorithm import learner_mesh

        self.policy = self._policy_cls(
            config, seed=config.seed,
            mesh=learner_mesh(config.learner_devices))
        spec = PolicySpec(obs_dim=config.obs_dim,
                          n_actions=config.n_actions,
                          hidden=tuple(config.hidden), lr=config.lr)
        remote_cls = ray_tpu.remote(
            num_cpus=config.num_cpus_per_worker)(TrajectoryWorker)
        self.workers = [
            remote_cls.remote(
                env=config.env, env_config=config.env_config,
                policy_spec=spec, num_envs=config.num_envs_per_worker,
                gamma=config.gamma,
                rollout_fragment_length=config.rollout_fragment_length,
                seed=config.seed + 1000 * (i + 1))
            for i in range(config.num_workers)]
        w0 = self.policy.get_weights()
        ray_tpu.get([w.set_weights.remote(w0) for w in self.workers],
                    timeout=120)
        #: ref -> worker, the async sample pipeline (reference:
        #: impala.py:610 sample refs tracked across training_steps).
        self._inflight: Dict[Any, Any] = {}
        self._learner_steps = 0
        for w in self.workers:
            for _ in range(config.max_requests_in_flight_per_worker):
                self._inflight[w.sample_trajectory.remote()] = w

    def training_step(self) -> Dict[str, Any]:
        import ray_tpu

        cfg = self.config
        steps = 0
        staged = None
        dev_stats = None
        frag = cfg.rollout_fragment_length * cfg.num_envs_per_worker
        while steps < cfg.train_batch_size:
            ready, _ = ray_tpu.wait(list(self._inflight), num_returns=1,
                                    timeout=300.0)
            if not ready:
                raise TimeoutError("no rollout arrived within 300s")
            for ref in ready:
                worker = self._inflight.pop(ref)
                host = ray_tpu.get(ref)
                # re-issue immediately: the worker keeps sampling while
                # the learner trains (async pipeline depth stays full)
                self._inflight[worker.sample_trajectory.remote()] = worker
                # Double buffer: train on the PREVIOUSLY staged batch
                # (device-resident) while this one transfers.
                incoming = self.policy.stage(host)
                if staged is not None:
                    dev_stats = self.policy.learn_staged(staged)
                    self._learner_steps += 1
                    self._maybe_broadcast()
                    steps += frag
                staged = incoming
        if staged is not None:
            dev_stats = self.policy.learn_staged(staged)
            self._learner_steps += 1
            self._maybe_broadcast()
            steps += frag
        stats = {k: float(v) for k, v in (dev_stats or {}).items()}
        self._collect_episode_returns()
        stats["timesteps_this_iter"] = steps
        stats["learner_steps"] = self._learner_steps
        return stats

    def _maybe_broadcast(self):
        import ray_tpu

        if self._learner_steps % self.config.broadcast_interval:
            return
        ref = ray_tpu.put(self.policy.get_weights())
        for w in self.workers:
            w.set_weights.remote(ref)  # fire and forget: stale is fine

    def _collect_episode_returns(self):
        import ray_tpu

        try:
            parts = ray_tpu.get(
                [w.pop_episode_returns.remote() for w in self.workers],
                timeout=60)
            self._episode_returns.extend(r for p in parts for r in p)
        except Exception:  # noqa: BLE001 - metrics only
            pass

    def cleanup(self) -> None:
        import ray_tpu

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        self.workers = []


@dataclasses.dataclass
class APPOConfig(IMPALAConfig):
    """APPO (reference: rllib/algorithms/appo/appo.py) — IMPALA's async
    architecture with the PPO clipped surrogate on v-trace advantages."""

    clip_param: float = 0.2


class APPOPolicy(IMPALAPolicy):
    def _policy_loss(self, target_logp, behaviour_logp, pg_adv):
        import jax.numpy as jnp

        ratio = jnp.exp(target_logp - behaviour_logp)
        clip = self.cfg.clip_param
        surr = jnp.minimum(
            ratio * pg_adv,
            jnp.clip(ratio, 1 - clip, 1 + clip) * pg_adv)
        return -jnp.mean(surr)


class APPO(IMPALA):
    _config_cls = APPOConfig
    _policy_cls = APPOPolicy
