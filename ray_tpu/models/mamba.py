"""The Mamba mixer two families run: a causal depthwise convolution
behind a window of its last inputs, and a selective state-space scan
over a state of (d_state, d_inner) a sequence.

`cfg` is any config with the mixer's fields (``d_inner``, ``d_state``,
``d_conv``, ``dt_rank``, ``dtype``, ``rms_eps``, ``scan_chunk``): no
family is named here.  `conv_inputs` alone is also what the delta-rule
families' three convolutions read (models/solar_open2.py,
models/olmo_hybrid.py).  What a family keeps of the state per slot, and
where, is its decode module's and decode_common.py's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.layers import plain_rmsnorm


def ssm_scan(dt, x, A, Bm, Cm, s0, chunk: int, capture=None):
    """The selective-scan recurrence over T columns, float32.

    dt, x (B, T, di); A (N, di); Bm, Cm (B, T, N); s0 (B, N, di).
    ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = sum_n
    s_t C_t``.  Returns (y (B, T, di), s_T, s after column `capture`
    or None).  `capture` is a traced column index; a column with
    ``dt = 0`` is the identity, exactly.

    Two bodies (ops/ssm_scan.py), chosen by what the shape says of the
    need.  One column (a decode step) is one elementwise expression
    over every row's state, bound by the state's bytes.  Several columns
    on a TPU are bound by the launches of the chain through them: there
    the Pallas kernel walks the columns with the state in VMEM.
    Elsewhere the ``jnp`` chain, `chunk` columns at a time, which is
    also what the kernel's backward pass differentiates."""
    from ray_tpu.ops.ssm_scan import (selective_scan,
                                      selective_scan_reference)

    if x.shape[1] > 1 and jax.default_backend() == "tpu":
        return selective_scan(dt, x, A, Bm, Cm, s0, capture,
                              ref_chunk=chunk)
    return selective_scan_reference(dt, x, A, Bm, Cm, s0, chunk, capture)


def conv_inputs(x, window, real=None):
    """What a causal depthwise convolution of kernel K reads: x (B, T,
    di), zero on its pads, behind the K - 1 inputs before it, `window`
    (K-1, B, di).  ``ext[:, K-1 + t] = x[:, t]``, the window directly
    before the row's first real column (`real` (B, T) bool, pads first);
    a row of one column that holds no token keeps its window.  The
    window after the last column is ``ext[:, T:]``.  (B, K-1 + T,
    di)."""
    B, T, di = x.shape
    K1 = window.shape[0]
    win = window.astype(x.dtype).swapaxes(0, 1)          # (B, K-1, di)
    if real is None:
        return jnp.concatenate([win, x], axis=1)
    if T == 1:
        held = jnp.concatenate([jnp.zeros_like(x), win], axis=1)
        return jnp.where(real[..., None],
                         jnp.concatenate([win, x], axis=1), held)
    pads = T - jnp.sum(real, axis=1).astype(jnp.int32)
    ext = jnp.concatenate([jnp.zeros((B, K1, di), x.dtype), x], axis=1)
    return jax.vmap(lambda e, w, at: lax.dynamic_update_slice(
        e, w, (at, 0)))(ext, win, pads)


def mamba_mix(p, u, cfg, window, state, real=None, capture=None):
    """The Mamba mixer on normalised input u (B, T, d).  `cfg` is any
    config with this one's mixer fields (models/phi4flash.py hands its
    own); a `p` without ``dt_norm``, ``b_norm``, ``c_norm`` is plain
    Mamba-1: ``dt``, ``B`` and ``C`` go unnormed.

    window (K-1, B, di): the convolution's last inputs, compute dtype;
    state (B, N, di): the SSM state.  real (B, T) bool marks the columns
    that hold a token: a row's pads come first, its tokens after them
    (left padding), and a pad moves neither window nor state.  capture:
    a traced column index (rows all alike) after which window and state
    are also handed back, for a snapshot.

    Returns (out (B, T, d), (window, state), (window, state) after
    `capture` or None, y (B, T, di) float32: the scan's output before
    its gate, with the ``D`` skip in it)."""
    B, T, _ = u.shape
    di, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank
    f32 = jnp.float32
    with jax.named_scope(scopes.SSM):
        xz = u.astype(cfg.dtype) @ p["in_proj"].astype(cfg.dtype)
        x, z = xz[..., :di], xz[..., di:]
        if real is not None:
            x = jnp.where(real[..., None], x, jnp.zeros((), x.dtype))
        ext = conv_inputs(x, window, real)
        new_window = ext[:, T:].swapaxes(0, 1)
        w = p["conv_w"].astype(f32)
        conv = sum(ext[:, k:k + T].astype(f32) * w[k] for k in range(K))
        xc = jax.nn.silu(conv + p["conv_b"].astype(f32))  # (B, T, di)
        dbc = jnp.einsum("btd,dr->btr", xc.astype(cfg.dtype),
                         p["x_proj"].astype(cfg.dtype),
                         preferred_element_type=f32)

        def normed(a, scale):
            if scale not in p:
                return a
            return plain_rmsnorm(a, p[scale].astype(f32), cfg.rms_eps)

        dt_in = normed(dbc[..., :R], "dt_norm")
        Bm = normed(dbc[..., R:R + N], "b_norm")
        Cm = normed(dbc[..., R + N:], "c_norm")
        dt = jax.nn.softplus(
            jnp.einsum("btr,rd->btd", dt_in.astype(cfg.dtype),
                       p["dt_proj"].astype(cfg.dtype),
                       preferred_element_type=f32)
            + p["dt_bias"].astype(f32))
        if real is not None:
            dt = jnp.where(real[..., None], dt, 0.0)
        A = -jnp.exp(p["A_log"].astype(f32))             # (N, di)
        y, new_state, snap_state = ssm_scan(
            dt, xc, A, Bm, Cm, state.astype(f32), cfg.scan_chunk,
            capture)
        y = y + p["D"].astype(f32) * xc
        out = (y * jax.nn.silu(z.astype(f32))).astype(cfg.dtype) \
            @ p["out_proj"].astype(cfg.dtype)
        snap = None
        if capture is not None:
            snap_window = lax.dynamic_slice_in_dim(
                ext, capture + 1, K - 1, axis=1).swapaxes(0, 1)
            snap = (snap_window.astype(window.dtype),
                    snap_state.astype(state.dtype))
    return (out.astype(u.dtype),
            (new_window.astype(window.dtype),
             new_state.astype(state.dtype)), snap, y)
