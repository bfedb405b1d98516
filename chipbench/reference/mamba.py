"""Mamba2 block (arXiv:2405.21060, section 7), with the selective state
space recurrence run step by step over time.

    h = rms_norm(x);  z = h W_z;  u = silu(causal_conv(h W_x))
    B_t = h_t W_B,  C_t = h_t W_C,  dt_t = softplus(h_t W_dt + dt_bias)
    s_t = exp(-exp(A_log) dt_t) s_{t-1} + dt_t u_t B_t^T    (per head)
    y_t = s_t C_t + D u_t
    out = x + rms_norm(y * silu(z)) W_out

Departures from the published block, as the repo's model has them: one
group of B and C for all heads; no bias on the convolution; no limits on
dt; the convolution covers u only, not B and C.
"""

from __future__ import annotations

import jax.numpy as jnp

from .common import F32, rms_norm, silu, softplus, time_scan


KEY = "mamba"


def param_shapes(cfg):
    d, N, W = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv_width"]
    e = cfg["ssm_expand"] * d
    nh = e // cfg["ssm_head_dim"]
    return {"ln": ((d,), "ones"), "w_z": ((d, e), None), "w_x": ((d, e), None),
            "w_B": ((d, N), None), "w_C": ((d, N), None),
            "w_dt": ((d, nh), None), "conv_w": ((W, e), 0.5),
            "A_log": ((nh,), "zeros"), "D": ((nh,), "ones"),
            "dt_bias": ((nh,), "zeros"), "gn": ((e,), "ones"),
            "w_out": ((e, d), None)}


def block(p, x, cfg, mm, shared=None):
    Bb, S, d = x.shape
    e = cfg["ssm_expand"] * d
    hd = cfg["ssm_head_dim"]
    nh = e // hd
    W = cfg["ssm_conv_width"]
    h = rms_norm(x, p["ln"], cfg["norm_eps"])
    z = mm("bsd,de->bse", h, p["w_z"])
    xin = mm("bsd,de->bse", h, p["w_x"])
    padded = jnp.pad(xin, ((0, 0), (W - 1, 0), (0, 0)))
    conv_w = p["conv_w"].astype(F32)
    u = silu(sum(padded[:, j:j + S] * conv_w[j] for j in range(W)))
    Bt = mm("bsd,dn->bsn", h, p["w_B"])
    Ct = mm("bsd,dn->bsn", h, p["w_C"])
    dt = softplus(mm("bsd,dh->bsh", h, p["w_dt"]) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    u = u.reshape(Bb, S, nh, hd)

    def step(s, inp):
        u_t, B_t, C_t, dt_t = inp
        s = s * jnp.exp(A * dt_t)[..., None, None] \
            + (dt_t[..., None] * u_t)[..., None] * B_t[:, None, None, :]
        return s, mm("bhdn,bn->bhd", s, C_t)

    s0 = jnp.zeros((Bb, nh, hd, Bt.shape[-1]), F32)
    _, ys = time_scan(step, s0, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (u, Bt, Ct, dt)))
    y = jnp.moveaxis(ys, 0, 1) + p["D"].astype(F32)[:, None] * u
    y = rms_norm(y.reshape(Bb, S, e) * silu(z), p["gn"], cfg["norm_eps"])
    return x + mm("bse,ed->bsd", y, p["w_out"])
