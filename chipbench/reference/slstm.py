"""sLSTM block (xLSTM, arXiv:2405.04517, section 2.2), as a sequential
recurrence over time.

Per head, with the previous output h_{t-1} fed back through a
block-diagonal recurrent matrix R:

    [z, i, f, o]_t = x_t W + h_{t-1} R
    i_t, f_t = mean over the head of the i and f pre-activations
    m_t = max(log sigmoid(f_t) + m_{t-1}, i_t)
    c_t = e^{log sigmoid(f_t) + m_{t-1} - m_t} c_{t-1} + e^{i_t - m_t} tanh(z_t)
    n_t = e^{log sigmoid(f_t) + m_{t-1} - m_t} n_{t-1} + e^{i_t - m_t}
    h_t = sigmoid(o_t) c_t / max(n_t, 1)
    y = rms_norm(h);  out = x + (up * gelu(gate)) W_down,  [up, gate] = y W_up

Departures from the paper, as the repo's model has them: the input and
forget gates are one scalar per head (the mean of the head's
pre-activations) where the paper has one per unit; the output divides by
max(n_t, 1) where the paper divides by n_t; there is no causal
convolution before the gates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, gelu_tanh, log_sigmoid, rms_norm, time_scan


KEY = "slstm"


def param_shapes(cfg):
    d, H = cfg["d_model"], cfg["n_heads"]
    f = int(4 * d / 3 / 64) * 64 or 64
    return {"ln": ((d,), "ones"), "w_zifo": ((d, 4 * d), None),
            "r_zifo": ((H, d // H, 4 * d // H), 0.1), "gn": ((d,), "ones"),
            "w_up": ((d, 2 * f), None), "w_down": ((f, d), None)}


def block(p, x, cfg, mm, shared=None):
    B, S, d = x.shape
    H = cfg["n_heads"]
    hd = d // H
    zifo = mm("bsd,df->bsf", rms_norm(x, p["ln"], cfg["norm_eps"]),
              p["w_zifo"]).reshape(B, S, H, 4 * hd)
    R = p["r_zifo"]

    def step(carry, g_in):
        c, n, h, m = carry
        g = g_in + mm("bhk,hkf->bhf", h, R)
        z, i, f, o = jnp.split(g, 4, axis=-1)
        i, f = i.mean(-1), f.mean(-1)
        logf = log_sigmoid(f)
        m_new = jnp.maximum(logf + m, i)
        fg = jnp.exp(logf + m - m_new)[..., None]
        ig = jnp.exp(i - m_new)[..., None]
        c = fg * c + ig * jnp.tanh(z)
        n = fg * n + ig
        h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    zeros = jnp.zeros((B, H, hd), F32)
    init = (zeros, zeros, zeros, jnp.full((B, H), -1e30, F32))
    _, hs = time_scan(step, init, jnp.moveaxis(zifo, 1, 0))
    y = rms_norm(jnp.moveaxis(hs, 0, 1).reshape(B, S, d), p["gn"],
                 cfg["norm_eps"])
    up, gate = jnp.split(mm("bsd,df->bsf", y, p["w_up"]), 2, axis=-1)
    return x + mm("bsf,fd->bsd", up * gelu_tanh(gate), p["w_down"])
