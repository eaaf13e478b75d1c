"""JAX policies.

The reference's JAX support is stubs only (rllib/models/jax/ — fcnet
scaffolding, no trainable policy); this is the real thing.  TPU-first
design: the whole PPO update — num_sgd_iter epochs over shuffled
minibatches — is ONE jitted call (`lax.scan` over minibatch indices), so
a training_step does a single host→device transfer and a single
dispatch, replacing the reference's loader-thread/tower-stack pipeline
(multi_gpu_learner_thread.py:20) with an XLA-compiled loop.

Networks come from the model catalog (models.py — reference analog
rllib/models/catalog.py:195): MLP towers for vector observations, conv
stacks for (H, W, C) pixels, and an optional LSTM wrapper
(``PolicySpec.use_lstm``) trained with truncated BPTT over
``max_seq_len`` chunks whose initial recurrent states were recorded at
rollout time (reference: policy/rnn_sequencing.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.models import (attention_apply, attention_init,
                                  Encoder, ModelConfig, lstm_init,
                                  lstm_step, mlp_apply, mlp_init)
from ray_tpu.rllib.sample_batch import SampleBatch

#: sequence-batch keys for recurrent policies (chunk-initial states)
STATE_H = "state_h"
STATE_C = "state_c"

# legacy aliases: earlier modules (dqn/impala) import the raw MLP
# helpers from here
_net_init = mlp_init
_net_apply = mlp_apply


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    obs_dim: int
    #: discrete: number of actions; continuous: action dimensionality
    #: (set continuous=True)
    n_actions: int
    hidden: Tuple[int, ...] = (64, 64)
    lr: float = 3e-4
    clip_param: float = 0.2
    vf_coeff: float = 0.5
    entropy_coeff: float = 0.01
    num_sgd_iter: int = 6
    minibatch_size: int = 128
    grad_clip: float = 0.5
    #: Box action spaces: diagonal-Gaussian policy (state-dependent mean,
    #: state-independent log_std — standard PPO parameterization).
    continuous: bool = False
    #: full observation shape; None → (obs_dim,).  Rank-3 shapes select
    #: the conv stack from the model catalog.
    obs_shape: Optional[Tuple[int, ...]] = None
    #: ((out_ch, kernel, stride), ...); None → catalog default by shape
    conv_filters: Optional[Tuple[Tuple[int, int, int], ...]] = None
    use_lstm: bool = False
    lstm_cell_size: int = 64
    #: BPTT / attention-context chunk length
    max_seq_len: int = 16
    #: GTrXL-style gated causal self-attention over the last
    #: max_seq_len steps (reference: attention_net.py:37 GTrXLNet).
    #: Context is chunk-local (resets every max_seq_len steps and at
    #: episode boundaries) so training replays rollouts EXACTLY.
    use_attention: bool = False
    attention_dim: int = 64
    attention_heads: int = 4

    @property
    def obs_shape_(self) -> Tuple[int, ...]:
        return tuple(self.obs_shape) if self.obs_shape else (self.obs_dim,)

    def model_config(self) -> ModelConfig:
        return ModelConfig(fcnet_hiddens=tuple(self.hidden),
                           conv_filters=self.conv_filters,
                           use_lstm=self.use_lstm,
                           lstm_cell_size=self.lstm_cell_size,
                           max_seq_len=self.max_seq_len)


class JaxPolicy:
    """Actor-critic policy with a PPO-clip update.

    Parameters live wherever jax puts them (TPU on the learner, CPU on
    rollout workers); `get_weights`/`set_weights` move numpy pytrees so
    weight broadcast rides the object store.

    Feedforward specs build two independent towers (pi, vf) as the
    reference's default (vf_share_layers=False); recurrent specs share
    one encoder+LSTM trunk with linear pi/vf heads (the reference's
    LSTM wrapper shape, recurrent_net.py).
    """

    def __init__(self, spec: PolicySpec, seed: int = 0, mesh=None):
        """mesh: a jax Mesh with a "data" axis — the learner update then
        runs data-parallel across its devices (params replicated, batch
        rows sharded, gradients psum'd by GSPMD)."""
        import jax
        import optax

        import jax.numpy as jnp

        self.mesh = mesh
        self.spec = spec
        self.encoder = Encoder(spec.obs_shape_, spec.model_config())
        key = jax.random.PRNGKey(seed)
        kp, kv, kl, kh1, kh2 = jax.random.split(key, 5)
        feat = self.encoder.feature_dim
        if spec.use_lstm and spec.use_attention:
            raise ValueError("use_lstm and use_attention are exclusive")
        if spec.use_lstm:
            cell = spec.lstm_cell_size
            self.params = {
                "enc": self.encoder.init(kp),
                "lstm": lstm_init(kl, feat, cell),
                "pi": mlp_init(kh1, (cell, spec.n_actions)),
                "vf": mlp_init(kh2, (cell, 1)),
            }
        elif spec.use_attention:
            dim = spec.attention_dim
            self.params = {
                "enc": self.encoder.init(kp),
                "att_in": mlp_init(kl, (feat, dim)),
                "att": attention_init(kh1, dim, spec.attention_heads,
                                      context_len=spec.max_seq_len),
                "pi": mlp_init(kh2, (dim, spec.n_actions)),
                "vf": mlp_init(kv, (dim, 1)),
            }
        else:
            self.params = {
                "pi": {"enc": self.encoder.init(kp),
                       "head": mlp_init(kh1, (feat, spec.n_actions))},
                "vf": {"enc": self.encoder.init(kv),
                       "head": mlp_init(kh2, (feat, 1))},
            }
        if spec.continuous:
            self.params["log_std"] = jnp.zeros((spec.n_actions,))
        self.tx = optax.chain(
            optax.clip_by_global_norm(spec.grad_clip),
            optax.adam(spec.lr))
        self.opt_state = self.tx.init(self.params)
        self._rng = jax.random.PRNGKey(seed + 1)
        #: live rollout recurrent state (numpy, (N, cell) x2)
        self._state: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._eval_state: Optional[Tuple[np.ndarray, np.ndarray]] = None
        #: attention rollout memory: encoded-feature ring of the current
        #: chunk, (N, L, dim); _mem_pos is chunk-global (the worker
        #: aligns fragments to max_seq_len), _mem_start[i] marks where
        #: env i's current episode began inside the chunk
        self._mem: Optional[np.ndarray] = None
        self._mem_pos = 0
        self._mem_start: Optional[np.ndarray] = None
        self._build_fns()

    # -- weights ----------------------------------------------------------
    def get_weights(self):
        import jax

        return jax.tree.map(np.asarray, self.params)

    def set_weights(self, weights) -> None:
        import jax
        import jax.numpy as jnp

        self.params = jax.tree.map(jnp.asarray, weights)

    # -- recurrent state --------------------------------------------------
    @property
    def is_recurrent(self) -> bool:
        return self.spec.use_lstm

    @property
    def needs_sequences(self) -> bool:
        """Training batches must be (S, max_seq_len, ...) chunks."""
        return self.spec.use_lstm or self.spec.use_attention

    # -- attention memory -------------------------------------------------
    def reset_memory(self, n: int) -> None:
        """New attention chunk: the worker calls this every max_seq_len
        steps so rollout context matches the chunk-local context
        training recomputes."""
        dim = self.spec.attention_dim
        self._mem = np.zeros((n, self.spec.max_seq_len, dim),
                             np.float32)
        self._mem_pos = 0
        self._mem_start = np.zeros(n, np.int64)

    def get_state(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Current rollout carry for n env copies (zero-init)."""
        cell = self.spec.lstm_cell_size
        if self._state is None or self._state[0].shape[0] != n:
            self._state = (np.zeros((n, cell), np.float32),
                           np.zeros((n, cell), np.float32))
        return self._state

    def reset_state_where(self, done: np.ndarray) -> None:
        """Zero the carry rows of finished envs (mirrors the done-mask
        reset inside the training scan); attention policies advance the
        episode-start marker instead (mirrors the segment mask)."""
        if self._state is not None and done.any():
            self._state[0][done] = 0.0
            self._state[1][done] = 0.0
        if self._mem_start is not None and done.any():
            self._mem_start[done] = self._mem_pos

    def reset_eval_state(self) -> None:
        self._eval_state = None
        self._eval_mem = None
        self._eval_pos = 0
        self._eval_start = None

    def reset_eval_state_where(self, done: np.ndarray) -> None:
        """Zero eval carries of finished episodes (the evaluation analog
        of reset_state_where)."""
        if self._eval_state is not None and done.any():
            self._eval_state[0][done] = 0.0
            self._eval_state[1][done] = 0.0
        if getattr(self, "_eval_start", None) is not None and done.any():
            self._eval_start[done] = self._eval_pos

    # -- network builders -------------------------------------------------
    def _build_fns(self):
        import jax
        import jax.numpy as jnp

        spec = self.spec
        enc = self.encoder

        def ff_logits_vf(params, obs):
            logits = mlp_apply(params["pi"]["head"],
                               enc.apply(params["pi"]["enc"], obs))
            vf = mlp_apply(params["vf"]["head"],
                           enc.apply(params["vf"]["enc"], obs))[..., 0]
            return logits, vf

        self._ff_logits_vf = jax.jit(ff_logits_vf)

        def rec_step(params, carry, obs):
            """One recurrent forward: carry x obs -> (carry', logits, vf)."""
            feats = enc.apply(params["enc"], obs)
            h, c = lstm_step(params["lstm"], carry, feats)
            logits = mlp_apply(params["pi"], h)
            vf = mlp_apply(params["vf"], h)[..., 0]
            return (h, c), logits, vf

        _half_log_2pi_e = 0.5 * (jnp.log(2 * jnp.pi) + 1.0)

        def _gaussian_logp(mean, log_std, actions):
            std = jnp.exp(log_std)
            return jnp.sum(
                -0.5 * jnp.square((actions - mean) / std)
                - log_std - 0.5 * jnp.log(2 * jnp.pi), axis=-1)

        def _sample(logits, vf, params, sub, greedy=False):
            if spec.continuous:
                log_std = params["log_std"]
                if greedy:
                    actions = logits
                else:
                    noise = jax.random.normal(sub, logits.shape)
                    actions = logits + jnp.exp(log_std) * noise
                logp = _gaussian_logp(logits, log_std, actions)
            else:
                if greedy:
                    actions = jnp.argmax(logits, axis=-1)
                else:
                    actions = jax.random.categorical(sub, logits)
                logp_all = jax.nn.log_softmax(logits)
                logp = jnp.take_along_axis(logp_all, actions[:, None],
                                           axis=-1)[:, 0]
            return actions, logp

        @jax.jit
        def act(params, obs, rng):
            logits, vf = ff_logits_vf(params, obs)
            rng, sub = jax.random.split(rng)
            actions, logp = _sample(logits, vf, params, sub)
            return actions, logp, vf, rng

        @jax.jit
        def act_greedy(params, obs):
            logits, _ = ff_logits_vf(params, obs)
            actions, _ = _sample(logits, None, params, None, greedy=True)
            return actions

        @jax.jit
        def act_rec(params, obs, rng, h, c):
            (h, c), logits, vf = rec_step(params, (h, c), obs)
            rng, sub = jax.random.split(rng)
            actions, logp = _sample(logits, vf, params, sub)
            return actions, logp, vf, rng, h, c

        @jax.jit
        def act_rec_greedy(params, obs, h, c):
            (h, c), logits, _ = rec_step(params, (h, c), obs)
            actions, _ = _sample(logits, None, params, None, greedy=True)
            return actions, h, c

        def _logp_entropy(params, logits, actions):
            if spec.continuous:
                log_std = params["log_std"]
                logp = _gaussian_logp(logits, log_std, actions)
                entropy = jnp.sum(log_std + _half_log_2pi_e)
            else:
                logp_all = jax.nn.log_softmax(logits)
                logp = jnp.take_along_axis(
                    logp_all,
                    actions[..., None].astype(jnp.int32),
                    axis=-1)[..., 0]
                entropy = -jnp.mean(
                    jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
            return logp, entropy

        def _ppo_objective(params, logp, entropy, vf, batch):
            ratio = jnp.exp(logp - batch[sb.ACTION_LOGP])
            adv = batch[sb.ADVANTAGES]
            surr = jnp.minimum(
                ratio * adv,
                jnp.clip(ratio, 1 - spec.clip_param,
                         1 + spec.clip_param) * adv)
            pi_loss = -jnp.mean(surr)
            vf_loss = jnp.mean(jnp.square(vf - batch[sb.VALUE_TARGETS]))
            total = pi_loss + spec.vf_coeff * vf_loss \
                - spec.entropy_coeff * entropy
            return total, {"policy_loss": pi_loss, "vf_loss": vf_loss,
                           "entropy": entropy, "total_loss": total}

        def ppo_loss(params, batch):
            logits, vf = ff_logits_vf(params, batch[sb.OBS])
            logp, entropy = _logp_entropy(params, logits,
                                          batch[sb.ACTIONS])
            return _ppo_objective(params, logp, entropy, vf, batch)

        n_heads = spec.attention_heads

        def att_features(params, obs_flat, S, L):
            feats = enc.apply(params["enc"], obs_flat)
            x = mlp_apply(params["att_in"], feats,
                          final_linear=False)
            return x.reshape(S, L, -1)

        def att_step(params, mem, pos, start, obs):
            """Shared context builder for every single-step attention
            path (act / greedy eval / value): encode obs, write slot
            ``pos``, attend causally over [start_i, pos], return the
            attended feature at ``pos`` plus the updated memory."""
            f = mlp_apply(
                params["att_in"], enc.apply(params["enc"], obs),
                final_linear=False)
            mem = mem.at[:, pos].set(f)
            L = mem.shape[1]
            idx = jnp.arange(L)
            valid = (idx[None, :] <= pos) & \
                    (idx[None, :] >= start[:, None])       # (N, L)
            mask = valid[:, None, :] & valid[:, :, None]   # (N, L, L)
            out = attention_apply(params["att"], mem, n_heads,
                                  mask=mask)
            return out[:, pos], mem

        @jax.jit
        def act_att(params, mem, pos, start, obs, rng):
            h, mem = att_step(params, mem, pos, start, obs)
            logits = mlp_apply(params["pi"], h)
            vf = mlp_apply(params["vf"], h)[..., 0]
            rng, sub = jax.random.split(rng)
            actions, logp = _sample(logits, vf, params, sub)
            return actions, logp, vf, rng, mem

        @jax.jit
        def act_att_greedy(params, mem, pos, start, obs):
            h, mem = att_step(params, mem, pos, start, obs)
            actions, _ = _sample(mlp_apply(params["pi"], h), None,
                                 params, None, greedy=True)
            return actions, mem

        def ppo_loss_att(params, batch):
            """Attention loss over (S, L, ...) chunks: causal attention
            with a segment mask cut at episode boundaries — the exact
            context the rollout used (chunk-local, start-marker
            resets)."""
            obs = batch[sb.OBS]
            S, L = obs.shape[0], obs.shape[1]
            x = att_features(
                params, obs.reshape((S * L,) + tuple(enc.obs_shape)),
                S, L)
            dones = batch[sb.DONES].astype(jnp.int32)
            # segment id = number of dones BEFORE each position
            seg = jnp.cumsum(
                jnp.concatenate([jnp.zeros((S, 1), jnp.int32),
                                 dones[:, :-1]], axis=1), axis=1)
            same = seg[:, :, None] == seg[:, None, :]
            out = attention_apply(params["att"], x, n_heads, mask=same)
            logits = mlp_apply(params["pi"], out)
            vf = mlp_apply(params["vf"], out)[..., 0]
            logp, entropy = _logp_entropy(params, logits,
                                          batch[sb.ACTIONS])
            return _ppo_objective(params, logp, entropy, vf, batch)

        def ppo_loss_seq(params, batch):
            """Recurrent loss over (S, L, ...) sequence chunks: encoder
            on the flattened steps, lax.scan over time with done-masked
            carry resets (reference: rnn_sequencing + LSTM loss)."""
            obs = batch[sb.OBS]
            S, L = obs.shape[0], obs.shape[1]
            feats = enc.apply(
                params["enc"],
                obs.reshape((S * L,) + tuple(enc.obs_shape)))
            feats = feats.reshape(S, L, -1)
            feats_t = jnp.swapaxes(feats, 0, 1)          # (L, S, F)
            dones_t = jnp.swapaxes(
                batch[sb.DONES].astype(jnp.float32), 0, 1)

            def step(carry, xs):
                feat, done = xs
                h, c = lstm_step(params["lstm"], carry, feat)
                mask = (1.0 - done)[:, None]
                return (h * mask, c * mask), h

            _, hs = jax.lax.scan(
                step, (batch[STATE_H], batch[STATE_C]),
                (feats_t, dones_t))
            hs = jnp.swapaxes(hs, 0, 1)                  # (S, L, cell)
            logits = mlp_apply(params["pi"], hs)
            vf = mlp_apply(params["vf"], hs)[..., 0]
            logp, entropy = _logp_entropy(params, logits,
                                          batch[sb.ACTIONS])
            return _ppo_objective(params, logp, entropy, vf, batch)

        loss_fn = (ppo_loss_seq if spec.use_lstm
                   else ppo_loss_att if spec.use_attention
                   else ppo_loss)
        mb = spec.minibatch_size

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, batch, rng):
            n = batch[sb.OBS].shape[0]
            mb_eff = min(mb, n)  # batches smaller than one minibatch
            n_mb = max(1, n // mb_eff)
            usable = n_mb * mb_eff

            def epoch(carry, key):
                params, opt_state = carry
                perm = jax.random.permutation(key, n)[:usable]
                idx = perm.reshape(n_mb, mb_eff)

                def mb_step(carry, rows):
                    params, opt_state = carry
                    mini = {k: v[rows] for k, v in batch.items()}
                    (loss, stats), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, mini)
                    updates, opt_state = self.tx.update(grads, opt_state,
                                                        params)
                    import optax

                    params = optax.apply_updates(params, updates)
                    return (params, opt_state), stats

                (params, opt_state), stats = jax.lax.scan(
                    mb_step, (params, opt_state), idx)
                return (params, opt_state), stats

            rng, *keys = jax.random.split(rng, spec.num_sgd_iter + 1)
            (params, opt_state), stats = jax.lax.scan(
                epoch, (params, opt_state), jnp.stack(keys))
            last = jax.tree.map(lambda s: s[-1, -1], stats)
            return params, opt_state, last, rng

        @jax.jit
        def value_att(params, mem, pos, start, obs):
            h, _ = att_step(params, mem, pos, start, obs)
            return mlp_apply(params["vf"], h)[..., 0]

        @jax.jit
        def value_ff(params, obs):
            return mlp_apply(params["vf"]["head"],
                             enc.apply(params["vf"]["enc"], obs))[..., 0]

        @jax.jit
        def value_rec(params, obs, h, c):
            _, _, vf = rec_step(params, (h, c), obs)
            return vf

        self._act = act
        self._act_greedy = act_greedy
        self._act_rec = act_rec
        self._act_rec_greedy = act_rec_greedy
        self._act_att = act_att
        self._act_att_greedy = act_att_greedy
        self._update = update
        self._loss = jax.jit(loss_fn)
        self._grad = jax.jit(lambda params, mini: jax.value_and_grad(
            loss_fn, has_aux=True)(params, mini))
        self._value_ff = value_ff
        self._value_rec = value_rec
        self._value_att = value_att

    # -- inference --------------------------------------------------------
    def compute_actions(self, obs: np.ndarray):
        if self.spec.use_attention:
            n = obs.shape[0]
            if self._mem is None or self._mem.shape[0] != n \
                    or self._mem_pos >= self.spec.max_seq_len:
                self.reset_memory(n)
            actions, logp, vf, self._rng, mem = self._act_att(
                self.params, self._mem, self._mem_pos,
                self._mem_start, obs, self._rng)
            self._mem = np.array(mem)
            self._mem_pos += 1
            return (np.asarray(actions), np.asarray(logp),
                    np.asarray(vf))
        if self.spec.use_lstm:
            h, c = self.get_state(obs.shape[0])
            actions, logp, vf, self._rng, h2, c2 = self._act_rec(
                self.params, obs, self._rng, h, c)
            # np.array (copy): reset_state_where writes into these rows,
            # and np.asarray on a jax array is a read-only view
            self._state = (np.array(h2), np.array(c2))
            return (np.asarray(actions), np.asarray(logp),
                    np.asarray(vf))
        actions, logp, vf, self._rng = self._act(self.params, obs,
                                                 self._rng)
        return (np.asarray(actions), np.asarray(logp), np.asarray(vf))

    def action_probs(self, obs: np.ndarray,
                     params=None) -> np.ndarray:
        """Action distribution at `obs` for feedforward policies —
        optionally under an EXTERNAL weight pytree with this policy's
        layout (league snapshot probes)."""
        import jax

        if self.spec.use_lstm or self.spec.use_attention \
                or self.spec.continuous:
            raise NotImplementedError(
                "action_probs serves feedforward categorical policies")
        obs = np.asarray(obs, np.float32)
        if obs.ndim == 1:
            obs = obs[None]
        logits, _ = self._ff_logits_vf(
            self.params if params is None else params, obs)
        return np.asarray(jax.nn.softmax(logits))

    def compute_deterministic_actions(self, obs: np.ndarray) -> np.ndarray:
        """Greedy/mean actions for evaluation (reference:
        explore=False in Algorithm.evaluate's policy calls)."""
        obs = np.asarray(obs, np.float32)
        if self.spec.use_attention:
            n = obs.shape[0]
            L = self.spec.max_seq_len
            if (getattr(self, "_eval_mem", None) is None
                    or self._eval_mem.shape[0] != n
                    or self._eval_pos >= L):
                self._eval_mem = np.zeros(
                    (n, L, self.spec.attention_dim), np.float32)
                self._eval_pos = 0
                self._eval_start = np.zeros(n, np.int64)
            actions, mem = self._act_att_greedy(
                self.params, self._eval_mem, self._eval_pos,
                self._eval_start, obs)
            self._eval_mem = np.array(mem)
            self._eval_pos += 1
            return np.asarray(actions)
        if self.spec.use_lstm:
            cell = self.spec.lstm_cell_size
            n = obs.shape[0]
            if (self._eval_state is None
                    or self._eval_state[0].shape[0] != n):
                self._eval_state = (np.zeros((n, cell), np.float32),
                                    np.zeros((n, cell), np.float32))
            actions, h, c = self._act_rec_greedy(
                self.params, obs, *self._eval_state)
            # np.array (copy): reset_eval_state_where writes these rows
            self._eval_state = (np.array(h), np.array(c))
            return np.asarray(actions)
        return np.asarray(self._act_greedy(self.params, obs))

    def value(self, obs: np.ndarray, rows=None) -> np.ndarray:
        """State values; for recurrent policies ``rows`` selects which
        env copies' live carries pair with ``obs`` (bootstrapping a
        done subset mid-rollout)."""
        obs = np.asarray(obs, np.float32)
        if self.spec.use_attention:
            n = obs.shape[0]
            L = self.spec.max_seq_len
            if self._mem is not None and self._mem_pos < L:
                mem, start = self._mem, self._mem_start
                if rows is not None:
                    mem, start = mem[rows], start[rows]
                pos = self._mem_pos
            else:
                # no context yet, or the chunk just filled: the NEXT
                # policy step sees a fresh chunk, so V(s) must be
                # computed in that same fresh context (overwriting the
                # last slot would silently drop obs_{T-1})
                mem = np.zeros((n, L, self.spec.attention_dim),
                               np.float32)
                start = np.zeros(n, np.int64)
                pos = 0
            return np.asarray(self._value_att(
                self.params, mem, pos, start, obs))
        if self.spec.use_lstm:
            n = obs.shape[0]
            if self._state is not None:
                h, c = self._state
                if rows is not None:
                    h, c = h[rows], c[rows]
            else:
                cell = self.spec.lstm_cell_size
                h = np.zeros((n, cell), np.float32)
                c = h
            return np.asarray(self._value_rec(self.params, obs, h, c))
        return np.asarray(self._value_ff(self.params, obs))

    # -- learning ---------------------------------------------------------
    def compute_gradients(self, batch: SampleBatch):
        """Gradients of the policy loss on `batch` WITHOUT applying
        them (reference: Policy.compute_gradients) — numpy pytree +
        stats, so gradients can cross the object store (DDPPO's
        allreduce-style data parallelism)."""
        import jax

        (_, stats), grads = self._grad(self.params, batch.to_device())
        return (jax.tree.map(np.asarray, grads),
                {k: float(v) for k, v in stats.items()})

    def apply_gradients(self, grads) -> None:
        """Apply externally computed (e.g. worker-averaged) gradients
        through this policy's optimizer (reference:
        Policy.apply_gradients)."""
        import optax

        updates, self.opt_state = self.tx.update(grads, self.opt_state,
                                                 self.params)
        self.params = optax.apply_updates(self.params, updates)

    def learn_on_batch(self, batch: SampleBatch) -> Dict[str, float]:
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            rows = NamedSharding(self.mesh, P("data"))
            n = batch.count
            shards = self.mesh.shape.get("data", 1)
            usable = (n // shards) * shards  # row axis must shard evenly
            dev = {k: jax.device_put(v[:usable], rows)
                   for k, v in batch.items()}
            self.params = jax.device_put(self.params, repl)
            self.opt_state = jax.device_put(self.opt_state, repl)
            with jax.set_mesh(self.mesh):
                (self.params, self.opt_state, stats,
                 self._rng) = self._update(self.params, self.opt_state,
                                           dev, self._rng)
        else:
            dev = batch.to_device()
            self.params, self.opt_state, stats, self._rng = self._update(
                self.params, self.opt_state, dev, self._rng)
        return {k: float(v) for k, v in stats.items()}
