"""DQN: off-policy Q-learning with replay (double-DQN + target network).

Reference analog: rllib/algorithms/dqn/ (training_step: sample into the
replay buffer, train on prioritized samples, update the target net).
TPU-first learner: `train_intensity` double-DQN gradient steps compile
into ONE jitted lax.scan call per training_step — minibatches are
presampled host-side from the replay buffer, stacked, and shipped in a
single host→device transfer (the same one-dispatch design as the PPO
learner in policy.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.policy import _net_apply, _net_init
from ray_tpu.rllib.replay_buffer import (PrioritizedReplayBuffer,
                                         ReplayBuffer)
from ray_tpu.rllib.sample_batch import SampleBatch

import ray_tpu


@dataclasses.dataclass(frozen=True)
class QPolicySpec:
    obs_dim: int
    n_actions: int
    hidden: Tuple[int, ...] = (64, 64)
    lr: float = 5e-4
    gamma: float = 0.99
    grad_clip: float = 10.0
    double_q: bool = True
    #: dueling streams: Q = V(s) + A(s,a) - mean_a A (Wang et al.;
    #: the reference DQN's default architecture)
    dueling: bool = True
    #: > 1: distributional C51 (reference DQNConfig.num_atoms) — the
    #: net emits a categorical return distribution per action over a
    #: fixed support [v_min, v_max]; TD projects the target
    #: distribution and minimizes cross-entropy
    num_atoms: int = 1
    v_min: float = -10.0
    v_max: float = 10.0
    #: NoisyNet exploration (Fortunato et al.; the reference's
    #: DQNConfig.noisy): the HEAD layers carry learned per-weight noise
    #: scales — exploration comes from resampling factorized Gaussian
    #: noise each forward instead of epsilon-greedy
    noisy: bool = False
    noisy_sigma0: float = 0.5

    @property
    def atom_support(self):
        import jax.numpy as jnp

        return jnp.linspace(self.v_min, self.v_max, self.num_atoms)


def _noisy_init(key, in_dim: int, out_dim: int, sigma0: float):
    """A factorized-noisy linear layer: mean weights + learned noise
    scales, initialized per Fortunato et al."""
    import jax
    import jax.numpy as jnp

    bound = 1.0 / np.sqrt(in_dim)
    kw, kb = jax.random.split(key)
    return {
        "w": jax.random.uniform(kw, (in_dim, out_dim), minval=-bound,
                                maxval=bound),
        "b": jax.random.uniform(kb, (out_dim,), minval=-bound,
                                maxval=bound),
        "w_sigma": jnp.full((in_dim, out_dim),
                            sigma0 / np.sqrt(in_dim)),
        "b_sigma": jnp.full((out_dim,), sigma0 / np.sqrt(in_dim)),
    }


def _noisy_apply(layer, x, key):
    """y = (w + w_sigma·eps_w) x + (b + b_sigma·eps_b) with factorized
    noise eps_w = f(eps_in) f(eps_out)^T, f(e) = sign(e)·sqrt|e|.
    key=None → mean weights only (evaluation / greedy play)."""
    import jax
    import jax.numpy as jnp

    if key is None:
        return x @ layer["w"] + layer["b"]
    k_in, k_out = jax.random.split(key)

    def f(e):
        return jnp.sign(e) * jnp.sqrt(jnp.abs(e))

    e_in = f(jax.random.normal(k_in, (layer["w"].shape[0],)))
    e_out = f(jax.random.normal(k_out, (layer["w"].shape[1],)))
    w = layer["w"] + layer["w_sigma"] * jnp.outer(e_in, e_out)
    b = layer["b"] + layer["b_sigma"] * e_out
    return x @ w + b


def _q_logits(spec: "QPolicySpec", params, obs, noise_key=None):
    """Per-action outputs: (B, n_actions) Q-values when num_atoms == 1,
    else (B, n_actions, num_atoms) distribution LOGITS.  Dueling
    combines streams in output space (Rainbow-style for atoms)."""
    import jax.numpy as jnp

    A = spec.num_atoms
    if spec.dueling or spec.noisy:
        h = _net_apply(params["trunk"], obs, final_linear=False)
        if spec.noisy:
            import jax

            kv = ka = None
            if noise_key is not None:
                kv, ka = jax.random.split(noise_key)
            v = _noisy_apply(params["v"], h, kv)
            a = _noisy_apply(params["a"], h, ka)
        else:
            v = _net_apply(params["v"], h)
            a = _net_apply(params["a"], h)
        if A > 1:
            v = v.reshape(v.shape[0], 1, A)
            a = a.reshape(a.shape[0], spec.n_actions, A)
            return v + a - jnp.mean(a, axis=1, keepdims=True)
        return v + a - jnp.mean(a, axis=-1, keepdims=True)
    out = _net_apply(params, obs)
    if A > 1:
        return out.reshape(out.shape[0], spec.n_actions, A)
    return out


def _q_apply(spec: "QPolicySpec", params, obs, noise_key=None):
    """Scalar Q-values under any architecture (atoms collapse to the
    distribution's expectation)."""
    import jax
    import jax.numpy as jnp

    out = _q_logits(spec, params, obs, noise_key)
    if spec.num_atoms > 1:
        probs = jax.nn.softmax(out, axis=-1)
        return jnp.sum(probs * spec.atom_support, axis=-1)
    return out


def _project_distribution(spec: "QPolicySpec", next_probs, rewards,
                          discounts):
    """C51 categorical projection: distribute P(Tz) onto the fixed
    support, Tz = r + disc·z clipped to [v_min, v_max]."""
    import jax.numpy as jnp

    z = spec.atom_support                          # (A,)
    dz = (spec.v_max - spec.v_min) / (spec.num_atoms - 1)
    tz = jnp.clip(rewards[:, None] + discounts[:, None] * z[None, :],
                  spec.v_min, spec.v_max)          # (B, A)
    b = (tz - spec.v_min) / dz
    lo = jnp.floor(b)
    hi = jnp.ceil(b)
    # mass splits between neighbors; lo==hi (on-grid) keeps it all
    w_lo = jnp.where(hi == lo, 1.0, hi - b)
    w_hi = b - lo
    B, A = next_probs.shape
    proj = jnp.zeros((B, A))
    rows = jnp.arange(B)[:, None].repeat(A, 1)
    proj = proj.at[rows, lo.astype(jnp.int32)].add(next_probs * w_lo)
    proj = proj.at[rows, hi.astype(jnp.int32)].add(next_probs * w_hi)
    return proj


class QPolicy:
    """Epsilon-greedy Q policy; the update is a jitted scan over
    presampled minibatches with a carried target network."""

    def __init__(self, spec: QPolicySpec, seed: int = 0, mesh=None):
        import jax
        import optax

        self.spec = spec
        self.mesh = mesh
        A = spec.num_atoms
        if spec.noisy and not spec.dueling:
            raise ValueError("noisy=True uses the trunk + v/a head "
                             "layout; set dueling=True as well")
        if spec.dueling or spec.noisy:
            kt, kv, ka = jax.random.split(jax.random.PRNGKey(seed), 3)
            feat = spec.hidden[-1] if spec.hidden else spec.obs_dim
            if spec.noisy:
                head = lambda k, w: _noisy_init(  # noqa: E731
                    k, feat, w, spec.noisy_sigma0)
            else:
                head = lambda k, w: _net_init(k, (feat, w))  # noqa: E731
            self.params = {
                "trunk": _net_init(kt, (spec.obs_dim, *spec.hidden)),
                "v": head(kv, A),
                "a": head(ka, spec.n_actions * A),
            }
        else:
            self.params = _net_init(jax.random.PRNGKey(seed),
                                    (spec.obs_dim, *spec.hidden,
                                     spec.n_actions * A))
        self.target_params = self._copy_tree(self.params)
        self.tx = optax.chain(optax.clip_by_global_norm(spec.grad_clip),
                              optax.adam(spec.lr))
        self.opt_state = self.tx.init(self.params)
        self._rng = np.random.RandomState(seed + 1)
        self._build_fns()

    def get_weights(self):
        import jax

        return jax.tree.map(np.asarray, self.params)

    def set_weights(self, weights) -> None:
        import jax
        import jax.numpy as jnp

        is_dueling_tree = (isinstance(weights, dict)
                           and {"trunk", "v", "a"} <= set(weights))
        if is_dueling_tree != self.spec.dueling:
            # e.g. restoring a pre-dueling checkpoint into the new
            # dueling-default policy: fail with the knob to flip
            # instead of a TypeError deep inside the jitted update
            raise ValueError(
                f"weight tree is "
                f"{'dueling' if is_dueling_tree else 'flat'} but this "
                f"policy was built with dueling={self.spec.dueling}; "
                f"set DQNConfig(dueling="
                f"{str(is_dueling_tree)}) to match the checkpoint")
        # same defense for the distributional width: a num_atoms
        # mismatch would otherwise surface as an opaque reshape error
        # inside the jitted forward
        if is_dueling_tree:
            checks = [("v", weights["v"], self.spec.num_atoms),
                      ("a", weights["a"],
                       self.spec.n_actions * self.spec.num_atoms)]
        else:
            checks = [("q", weights,
                       self.spec.n_actions * self.spec.num_atoms)]
        for name, head, want_width in checks:
            if name in ("v", "a"):
                is_noisy_head = (isinstance(head, dict)
                                 and "w_sigma" in head)
                if is_noisy_head != self.spec.noisy:
                    raise ValueError(
                        f"{name}-head is "
                        f"{'noisy' if is_noisy_head else 'plain'} but "
                        f"this policy was built with noisy="
                        f"{self.spec.noisy}; set DQNConfig(noisy="
                        f"{is_noisy_head}) to match the checkpoint")
            bias = (head["b"] if isinstance(head, dict)
                    else head[-1]["b"])
            got_width = int(np.asarray(bias).shape[-1])
            if got_width != want_width:
                raise ValueError(
                    f"{name}-head width {got_width} does not match "
                    f"this policy's (num_atoms={self.spec.num_atoms}, "
                    f"n_actions={self.spec.n_actions}); set "
                    f"DQNConfig(num_atoms=.../n_actions) to match the "
                    f"checkpoint")
        self.params = jax.tree.map(jnp.asarray, weights)

    @staticmethod
    def _copy_tree(tree):
        """Fresh device buffers — the update donates `params`, so the
        target net must never alias them (f(donate(a), a) is an error)."""
        import jax
        import jax.numpy as jnp

        return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)

    def sync_target(self) -> None:
        self.target_params = self._copy_tree(self.params)

    def _build_fns(self):
        import functools

        import jax
        import jax.numpy as jnp

        spec = self.spec

        @jax.jit
        def q_values(params, obs):
            return _q_apply(spec, params, obs)

        @jax.jit
        def q_values_noisy(params, obs, key):
            return _q_apply(spec, params, obs, key)

        def _discounts(mini):
            disc = mini.get("discounts")
            if disc is None:
                # 1-step path: γ·(1-done).  n-step workers ship a
                # per-transition "discounts" column = γ^k·(1-terminal)
                # (k = actual window length — shorter at episode ends
                # and fragment tails)
                disc = spec.gamma * (
                    1.0 - mini[sb.DONES].astype(jnp.float32))
            return disc

        def _keys(key, n):
            if key is None:
                return [None] * n
            return list(jax.random.split(key, n))

        def _best_next(params, target_params, mini, keys):
            q_next_tgt = _q_apply(spec, target_params,
                                  mini[sb.NEXT_OBS], keys[0])
            if spec.double_q:
                # action argmax by the ONLINE net, value by the target
                # net (van Hasselt double-DQN)
                q_next_online = _q_apply(
                    spec, params, mini[sb.NEXT_OBS], keys[1])
                return jnp.argmax(q_next_online, axis=-1), q_next_tgt
            return jnp.argmax(q_next_tgt, axis=-1), q_next_tgt

        def td_error(params, target_params, mini, key=None):
            ks = _keys(key, 3)
            q = _q_apply(spec, params, mini[sb.OBS], ks[2])
            qa = jnp.take_along_axis(
                q, mini[sb.ACTIONS][:, None].astype(jnp.int32),
                axis=-1)[:, 0]
            best, q_next_tgt = _best_next(params, target_params,
                                          mini, ks)
            v_next = jnp.take_along_axis(q_next_tgt, best[:, None],
                                         axis=-1)[:, 0]
            target = mini[sb.REWARDS] + _discounts(mini) * v_next
            return qa - jax.lax.stop_gradient(target)

        def c51_ce(params, target_params, mini, key=None):
            """Per-sample cross-entropy of the chosen action's return
            distribution against the projected target distribution —
            the C51 loss AND the priority signal."""
            ks = _keys(key, 3)
            logits = _q_logits(spec, params, mini[sb.OBS],
                               ks[2])                       # (B,n,A)
            acts = mini[sb.ACTIONS].astype(jnp.int32)
            chosen = jnp.take_along_axis(
                logits, acts[:, None, None].repeat(
                    spec.num_atoms, 2), axis=1)[:, 0]       # (B, A)
            logp = jax.nn.log_softmax(chosen, axis=-1)
            # ONE target forward: best-action selection reuses these
            # logits (expectation) instead of a second pass
            nlog_t = _q_logits(spec, target_params,
                               mini[sb.NEXT_OBS], ks[0])
            tgt_probs = jax.nn.softmax(nlog_t, axis=-1)
            q_next_tgt = jnp.sum(tgt_probs * spec.atom_support,
                                 axis=-1)                   # (B, n)
            if spec.double_q:
                best = jnp.argmax(
                    _q_apply(spec, params, mini[sb.NEXT_OBS], ks[1]),
                    axis=-1)
            else:
                best = jnp.argmax(q_next_tgt, axis=-1)
            next_dist = jnp.take_along_axis(
                tgt_probs, best[:, None, None].repeat(
                    spec.num_atoms, 2), axis=1)[:, 0]
            proj = _project_distribution(
                spec, next_dist, mini[sb.REWARDS], _discounts(mini))
            return -jnp.sum(jax.lax.stop_gradient(proj) * logp,
                            axis=-1)

        def loss_fn(params, target_params, mini, key=None):
            w = mini.get("is_weights")
            if spec.num_atoms > 1:
                ce = c51_ce(params, target_params, mini, key)
                loss = jnp.mean(ce * w) if w is not None \
                    else jnp.mean(ce)
                return loss, ce
            td = td_error(params, target_params, mini, key)
            huber = jnp.where(jnp.abs(td) < 1.0, 0.5 * td * td,
                              jnp.abs(td) - 0.5)
            if w is not None:
                huber = huber * w
            return jnp.mean(huber), td

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, target_params, stacked, rng):
            """stacked: pytree of (n_steps, minibatch, ...) arrays."""
            import optax

            def step(carry, mini):
                params, opt_state, rng = carry
                key = None
                if spec.noisy:
                    rng, key = jax.random.split(rng)
                (loss, td), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, target_params,
                                           mini, key)
                updates, opt_state = self.tx.update(grads, opt_state,
                                                    params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state, rng), (loss, td)

            (params, opt_state, rng), (losses, tds) = jax.lax.scan(
                step, (params, opt_state, rng), stacked)
            return params, opt_state, losses.mean(), tds, rng

        self._q_values = q_values
        self._q_values_noisy = q_values_noisy
        self._update = update
        self._train_rng = jax.random.PRNGKey(
            int(self._rng.randint(0, 2**31 - 1)))

    # -- inference --------------------------------------------------------
    def compute_actions(self, obs: np.ndarray,
                        epsilon: float = 0.0) -> np.ndarray:
        if self.spec.noisy and epsilon > 0.0:
            # NoisyNet: exploration comes from resampled weight noise,
            # not epsilon (epsilon>0 marks "exploring" rollouts;
            # epsilon==0 keeps greedy mean-weight evaluation)
            import jax

            self._train_rng, k = jax.random.split(self._train_rng)
            q = np.asarray(self._q_values_noisy(self.params, obs, k))
            return q.argmax(axis=-1)
        q = np.asarray(self._q_values(self.params, obs))
        greedy = q.argmax(axis=-1)
        if epsilon <= 0.0:
            return greedy
        explore = self._rng.rand(len(obs)) < epsilon
        rand = self._rng.randint(0, self.spec.n_actions, size=len(obs))
        return np.where(explore, rand, greedy)

    # -- learning ---------------------------------------------------------
    def learn_on_minibatches(self, minis: List[SampleBatch]
                             ) -> Tuple[float, np.ndarray]:
        """Run one jitted scan over the presampled minibatches; returns
        (mean_loss, td_errors of the LAST minibatch) for priority
        updates."""
        import jax.numpy as jnp

        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            rows = NamedSharding(self.mesh, P(None, "data"))
            repl = NamedSharding(self.mesh, P())
            # stack on HOST: one sharded transfer instead of a default-
            # device upload followed by a device-to-device reshard
            stacked = {k: jax.device_put(
                np.stack([m[k] for m in minis]), rows)
                for k in minis[0].keys()}
            self.params = jax.device_put(self.params, repl)
            self.opt_state = jax.device_put(self.opt_state, repl)
            self.target_params = jax.device_put(self.target_params, repl)
            with jax.set_mesh(self.mesh):
                (self.params, self.opt_state, loss, tds,
                 self._train_rng) = self._update(
                    self.params, self.opt_state, self.target_params,
                    stacked, self._train_rng)
            return float(loss), np.asarray(tds)
        stacked = {k: jnp.stack([m[k] for m in minis])
                   for k in minis[0].keys()}
        (self.params, self.opt_state, loss, tds,
         self._train_rng) = self._update(
            self.params, self.opt_state, self.target_params, stacked,
            self._train_rng)
        return float(loss), np.asarray(tds)


def _nstep_transitions(rew, done, boundary, next_obs,
                       gamma: float, n: int):
    """Fold (T, ...) per-env transitions into n-step ones: reward =
    Σ γ^j r, next_obs = the window's last successor, discounts =
    γ^k·(1-terminal); windows cut at episode boundaries (term OR
    trunc) and at the fragment tail."""
    T = len(rew)
    R = np.zeros(T, np.float32)
    nxt = np.array(next_obs)
    dn = np.array(done)
    disc = np.zeros(T, np.float32)
    for t in range(T):
        acc, g, k = 0.0, 1.0, 0
        terminal = False
        for j in range(n):
            if t + j >= T:
                break
            acc += g * float(rew[t + j])
            g *= gamma
            k = j
            if done[t + j]:
                terminal = True
                break
            if boundary[t + j]:          # truncation: stop, bootstrap
                break
        R[t] = acc
        nxt[t] = next_obs[t + k]
        dn[t] = bool(done[t + k])
        disc[t] = 0.0 if terminal else g
    return R, nxt, dn, disc


class TransitionWorker:
    """CPU actor collecting (obs, action, reward, next_obs, done)
    transitions with epsilon-greedy exploration (the off-policy
    counterpart of RolloutWorker; reference: the sampling half of DQN's
    training_step).  n_step > 1 folds each transition's reward over the
    next n steps (reference DQNConfig.n_step)."""

    def __init__(self, *, env: Any, env_config: Optional[Dict] = None,
                 spec: QPolicySpec, num_envs: int = 1,
                 rollout_fragment_length: int = 50, seed: int = 0,
                 n_step: int = 1):
        import os

        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from ray_tpu.rllib.rollout_worker import _make_env

        self.envs = [_make_env(env, env_config) for _ in range(num_envs)]
        self.policy = QPolicy(spec, seed=seed)
        self.n_step = max(1, int(n_step))
        self.fragment = rollout_fragment_length
        self._obs = [e.reset(seed=seed + i)[0]
                     for i, e in enumerate(self.envs)]
        self._ep_rewards = [0.0] * num_envs
        self.episode_returns: List[float] = []

    def set_weights(self, weights) -> None:
        self.policy.set_weights(weights)

    def sample(self, epsilon: float) -> SampleBatch:
        n_env = len(self.envs)
        T = self.fragment
        shape = (T, n_env)
        obs_buf = np.zeros(shape + np.shape(self._obs[0]), np.float32)
        next_buf = np.zeros_like(obs_buf)
        act_buf = np.zeros(shape, np.int64)
        rew_buf = np.zeros(shape, np.float32)
        done_buf = np.zeros(shape, np.bool_)
        bound_buf = np.zeros(shape, np.bool_)
        for t in range(T):
            obs = np.stack(self._obs).astype(np.float32)
            actions = self.policy.compute_actions(obs, epsilon=epsilon)
            obs_buf[t] = obs
            act_buf[t] = actions
            for i, env in enumerate(self.envs):
                o2, r, term, trunc, _ = env.step(int(actions[i]))
                rew_buf[t, i] = r
                self._ep_rewards[i] += r
                # time-limit truncation is NOT a terminal for bootstrap
                done_buf[t, i] = term
                bound_buf[t, i] = term or trunc
                next_buf[t, i] = np.asarray(o2, np.float32)
                if term or trunc:
                    self.episode_returns.append(self._ep_rewards[i])
                    self._ep_rewards[i] = 0.0
                    o2 = env.reset()[0]
                self._obs[i] = o2
        if self.n_step > 1:
            g = self.policy.spec.gamma
            disc_buf = np.zeros(shape, np.float32)
            for i in range(n_env):
                (rew_buf[:, i], next_buf[:, i], done_buf[:, i],
                 disc_buf[:, i]) = _nstep_transitions(
                    rew_buf[:, i], done_buf[:, i], bound_buf[:, i],
                    next_buf[:, i], g, self.n_step)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
        out = {
            sb.OBS: flat(obs_buf), sb.ACTIONS: flat(act_buf),
            sb.REWARDS: flat(rew_buf), sb.DONES: flat(done_buf),
            sb.NEXT_OBS: flat(next_buf)}
        if self.n_step > 1:
            out["discounts"] = flat(disc_buf)
        return SampleBatch(out)

    def pop_episode_returns(self) -> List[float]:
        out = self.episode_returns
        self.episode_returns = []
        return out


def linear_epsilon(env_steps: int, cfg) -> float:
    """The linear exploration schedule shared by DQN/R2D2/QMIX: decay
    epsilon_initial → epsilon_final over epsilon_decay_steps."""
    frac = min(1.0, env_steps / max(1, cfg.epsilon_decay_steps))
    return cfg.epsilon_initial + frac * (cfg.epsilon_final -
                                         cfg.epsilon_initial)


@dataclasses.dataclass
class DQNConfig(AlgorithmConfig):
    hidden: Tuple[int, ...] = (64, 64)
    lr: float = 5e-4
    buffer_size: int = 50_000
    prioritized_replay: bool = False
    prioritized_alpha: float = 0.6
    prioritized_beta: float = 0.4
    learning_starts: int = 1000
    train_batch_size: int = 32          # minibatch rows per SGD step
    train_intensity: int = 8            # SGD steps per training_step
    target_update_freq: int = 500       # env steps between target syncs
    epsilon_initial: float = 1.0
    epsilon_final: float = 0.02
    epsilon_decay_steps: int = 10_000
    double_q: bool = True
    dueling: bool = True
    #: fold rewards over n steps before TD (reference DQNConfig.n_step)
    n_step: int = 1
    #: > 1: distributional C51 head (reference DQNConfig.num_atoms)
    num_atoms: int = 1
    v_min: float = -10.0
    v_max: float = 10.0
    #: NoisyNet head exploration (reference DQNConfig.noisy); replaces
    #: epsilon-greedy when on
    noisy: bool = False
    noisy_sigma0: float = 0.5
    rollout_fragment_length: int = 50
    obs_dim: Optional[int] = None
    n_actions: Optional[int] = None
    #: >1: the TD update runs data-parallel over this many local devices
    learner_devices: int = 1

    def q_spec(self) -> QPolicySpec:
        return QPolicySpec(obs_dim=self.obs_dim,
                           n_actions=self.n_actions,
                           hidden=tuple(self.hidden), lr=self.lr,
                           gamma=self.gamma, double_q=self.double_q,
                           dueling=self.dueling,
                           num_atoms=self.num_atoms, v_min=self.v_min,
                           v_max=self.v_max, noisy=self.noisy,
                           noisy_sigma0=self.noisy_sigma0)


class DQN(Algorithm):
    _config_cls = DQNConfig

    def setup(self, config: DQNConfig) -> None:
        from ray_tpu.rllib.ppo import _introspect_spaces

        _introspect_spaces(config)
        spec = config.q_spec()
        if config.learner_devices > 1 and \
                config.train_batch_size % config.learner_devices:
            raise ValueError(
                f"train_batch_size={config.train_batch_size} must divide "
                f"by learner_devices={config.learner_devices} (the "
                f"minibatch row axis shards across the mesh)")
        from ray_tpu.rllib.algorithm import learner_mesh

        self.policy = QPolicy(spec, seed=config.seed,
                              mesh=learner_mesh(config.learner_devices))
        if config.prioritized_replay:
            self.buffer: ReplayBuffer = PrioritizedReplayBuffer(
                config.buffer_size, alpha=config.prioritized_alpha,
                beta=config.prioritized_beta, seed=config.seed)
        else:
            self.buffer = ReplayBuffer(config.buffer_size,
                                       seed=config.seed)
        remote_cls = ray_tpu.remote(
            num_cpus=config.num_cpus_per_worker)(TransitionWorker)
        self.workers = [
            remote_cls.remote(
                env=config.env, env_config=config.env_config, spec=spec,
                num_envs=config.num_envs_per_worker,
                rollout_fragment_length=config.rollout_fragment_length,
                seed=config.seed + 1000 * (i + 1),
                n_step=config.n_step)
            for i in range(config.num_workers)]
        self._env_steps = 0
        self._last_target_sync = 0

    def _epsilon(self) -> float:
        return linear_epsilon(self._env_steps, self.config)

    def _replay_learn_round(self) -> Optional[float]:
        """One learner round off the replay buffer: train_intensity
        jitted TD steps, priority feedback, scheduled target sync.
        Returns the mean loss, or None while the buffer is warming up.
        Shared by sync DQN and the async variants (ApexDQN)."""
        c = self.config
        if len(self.buffer) < max(c.learning_starts,
                                  c.train_batch_size):
            return None
        minis, idx_w = [], []
        for _ in range(c.train_intensity):
            if isinstance(self.buffer, PrioritizedReplayBuffer):
                mini, idx, w = self.buffer.sample(c.train_batch_size)
                mini["is_weights"] = w
                idx_w.append(idx)
            else:
                mini = self.buffer.sample(c.train_batch_size)
            minis.append(mini)
        loss, tds = self.policy.learn_on_minibatches(minis)
        if idx_w:
            # feed back every step's TD errors (tds rows align with
            # the sampled minibatches in order)
            for idx, td in zip(idx_w, tds):
                self.buffer.update_priorities(idx, td)
        if (self._env_steps - self._last_target_sync
                >= c.target_update_freq):
            self.policy.sync_target()
            self._last_target_sync = self._env_steps
        return loss

    def training_step(self) -> Dict[str, Any]:
        c = self.config
        eps = self._epsilon()
        parts = ray_tpu.get([w.sample.remote(eps) for w in self.workers],
                            timeout=300.0)
        for p in parts:
            self.buffer.add(p)
            self._env_steps += p.count

        stats: Dict[str, Any] = {"epsilon": eps,
                                 "buffer_size": len(self.buffer),
                                 "timesteps_this_iter":
                                     sum(p.count for p in parts)}
        loss = self._replay_learn_round()
        if loss is not None:
            stats["loss"] = loss
            weights = self.policy.get_weights()
            ref = ray_tpu.put(weights)
            ray_tpu.get([w.set_weights.remote(ref) for w in self.workers],
                        timeout=60.0)

        returns = ray_tpu.get(
            [w.pop_episode_returns.remote() for w in self.workers],
            timeout=60.0)
        self._episode_returns.extend(r for p in returns for r in p)
        return stats

    def cleanup(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        self.workers = []
