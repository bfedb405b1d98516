"""Zamba2's Mamba2 layer (arXiv:2405.21060, section 7; Zyphra/Zamba2-7B's
config.json and the transformers ``Zamba2MambaMixer``), with the selective
state space recurrence run step by step over time, at the share of the
layer that one chip holds.

    h = rms_norm(x + t)      t: a hybrid layer's shared-block output, else 0
    z = h W_z;  xBC = silu(causal_conv([h W_x | h W_B | h W_C]) + b_conv)
    u, B, C = split(xBC)     B and C per group: G groups of d_state
    dt = softplus(h W_dt + dt_bias)
    s_t = exp(-exp(A_log) dt_t) s_{t-1} + dt_t u_t B_t^T    (per head; head
    y_t = s_t C_t + D u_t                                     h reads group
                                                              h // (heads/G))
    out = x + group_rms_norm(y * silu(z)) W_out    (groups of e / G channels)

The published in-projection is one matrix onto [z | x B C | dt]; here its
parts are separate matrices, which is the same product.  dt has no floor
or ceiling (the configuration's ``time_step_limit`` is null).

The share: tensor parallelism splits the layer over ``ranks`` chips, each
holding 1/ranks of the heads and of the groups.  The shared attention's
heads span the 2d-wide concat(x, embedding) whole, so the heads of it that
the configuration states (``n_heads`` of ``head_dim``) give ``ranks``.
"""

from __future__ import annotations

import jax.numpy as jnp

from .common import F32, rms_norm, silu, softplus, time_scan

KEY = "mamba"
GROUPS = 2          # mamba_ngroups of the published model


def share(cfg) -> dict:
    """ranks, and the Mamba2 heads, inner width and groups on one chip."""
    d, hd = cfg["d_model"], cfg["ssm_head_dim"]
    ranks = 2 * d // (cfg["n_heads"] * cfg["head_dim"])
    heads = cfg["ssm_expand"] * d // hd // ranks
    return {"ranks": ranks, "heads": heads, "e": heads * hd,
            "groups": GROUPS // ranks}


def param_shapes(cfg):
    d, N, W = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv_width"]
    s = share(cfg)
    e, nh, bc = s["e"], s["heads"], s["groups"] * N
    return {"ln": ((d,), "ones"), "w_z": ((d, e), None),
            "w_x": ((d, e), None), "w_B": ((d, bc), None),
            "w_C": ((d, bc), None), "w_dt": ((d, nh), None),
            "conv_w": ((W, e + 2 * bc), 0.5), "conv_b": ((e + 2 * bc,), 0.5),
            "A_log": ((nh,), 0.5), "D": ((nh,), 1.0),
            "dt_bias": ((nh,), 0.5), "gn": ((e,), "ones"),
            "w_out": ((e, d), None)}


def mixer(p, h, cfg, mm):
    """The mixer of the normed input ``h``, without the residual."""
    Bb, S, _ = h.shape
    s = share(cfg)
    e, nh, G = s["e"], s["heads"], s["groups"]
    N, W, hd = cfg["ssm_state"], cfg["ssm_conv_width"], cfg["ssm_head_dim"]
    z = mm("bsd,de->bse", h, p["w_z"])
    xbc = jnp.concatenate([mm("bsd,de->bse", h, p[k])
                           for k in ("w_x", "w_B", "w_C")], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv_w = p["conv_w"].astype(F32)
    xbc = silu(sum(padded[:, j:j + S] * conv_w[j] for j in range(W))
               + p["conv_b"].astype(F32))
    u = xbc[..., :e].reshape(Bb, S, nh, hd)
    Bt, Ct = (xbc[..., lo:lo + G * N].reshape(Bb, S, G, N)
              for lo in (e, e + G * N))
    dt = softplus(mm("bsd,dh->bsh", h, p["w_dt"]) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    group = jnp.arange(nh) // (nh // G)    # head h reads group h // (nh / G)

    def step(st, inp):
        u_t, B_t, C_t, dt_t = inp
        st = st * jnp.exp(A * dt_t)[..., None, None] \
            + (dt_t[..., None] * u_t)[..., None] * B_t[:, group, None, :]
        return st, mm("bhdn,bhn->bhd", st, C_t[:, group])

    s0 = jnp.zeros((Bb, nh, hd, N), F32)
    _, ys = time_scan(step, s0, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (u, Bt, Ct, dt)))
    y = jnp.moveaxis(ys, 0, 1) + p["D"].astype(F32)[:, None] * u
    y = (y.reshape(Bb, S, e) * silu(z)).reshape(Bb, S, G, e // G)
    y = rms_norm(y, p["gn"].reshape(G, e // G), cfg["norm_eps"])
    return mm("bse,ed->bsd", y.reshape(Bb, S, e), p["w_out"])


def block(p, x, cfg, mm, shared=None, extra=None):
    """x + mixer(rms_norm(x + extra)): ``extra`` enters the mixer's input
    only (a hybrid layer's shared-block output)."""
    h = x if extra is None else x + extra
    return x + mixer(p, rms_norm(h, p["ln"], cfg["norm_eps"]), cfg, mm)
