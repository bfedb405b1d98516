"""mLSTM block (xLSTM, arXiv:2405.04517, section 2.3 and appendix B.3),
in the parallel form: a masked decay matrix over all pairs of positions.

    u = silu(rms_norm(x) W_up);   q = u Wq,  k = u Wk / sqrt(hd),  v = u Wv
    i_t = u W_i,  f_t = u W_f  (one raw gate per head)
    D_ts = F_t - F_s + i_s  (s <= t),   F_t = sum_{r<=t} log sigmoid(f_r)
    m_t = max_s D_ts,   A_ts = (q_t . k_s) exp(D_ts - m_t)
    h_t = (A v)_t / max(|sum_s A_ts|, 1)
    out = x + (h * silu(u W_o)) W_down

Departures from the paper, as the repo's model has them: the normaliser
is max(|n_t . q_t|, 1) in the stabilised scale where the paper has
max(|n_t . q_t|, exp(-m_t)); there is no causal convolution, no learnable
skip and no group norm inside the cell; q, k, v come from the whole up
projection and not from blocks of it.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from .common import log_sigmoid, rms_norm, silu


KEY = "mlstm"


def param_shapes(cfg):
    d, H = cfg["d_model"], cfg["n_heads"]
    e = 2 * d
    return {"ln": ((d,), "ones"), "w_up": ((d, e), None),
            "wq": ((e, e), None), "wk": ((e, e), None), "wv": ((e, e), None),
            "w_i": ((e, H), None), "w_f": ((e, H), None),
            "w_o": ((e, e), None), "w_down": ((e, d), None)}


def block(p, x, cfg, mm, shared=None):
    B, S, d = x.shape
    H = cfg["n_heads"]
    e = p["w_up"].shape[1]
    hd = e // H
    u = silu(mm("bsd,de->bse", rms_norm(x, p["ln"], cfg["norm_eps"]),
                p["w_up"]))
    q = mm("bse,ef->bsf", u, p["wq"]).reshape(B, S, H, hd)
    k = mm("bse,ef->bsf", u, p["wk"]).reshape(B, S, H, hd) / math.sqrt(hd)
    v = mm("bse,ef->bsf", u, p["wv"]).reshape(B, S, H, hd)
    i = mm("bse,eh->bhs", u, p["w_i"])
    F = jnp.cumsum(log_sigmoid(mm("bse,eh->bhs", u, p["w_f"])), axis=-1)
    causal = jnp.tril(jnp.ones((S, S), bool))
    D = jnp.where(causal, F[..., :, None] - F[..., None, :] + i[..., None, :],
                  -jnp.inf)
    m = jnp.max(D, axis=-1, keepdims=True)
    A = mm("bthk,bshk->bhts", q, k) * jnp.exp(D - m)
    num = mm("bhts,bshk->bthk", A, v)
    den = jnp.abs(jnp.sum(A, axis=-1))                    # (B, H, S)
    h = num / jnp.maximum(jnp.moveaxis(den, 1, 2), 1.0)[..., None]
    h = h.reshape(B, S, e) * silu(mm("bse,ef->bsf", u, p["w_o"]))
    return x + mm("bse,ed->bsd", h, p["w_down"])
