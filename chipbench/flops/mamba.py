"""Mamba2 layer: the projections (z, x, B, C, dt, out), the depthwise
convolution, and the state space recurrence in its recurrent form, the
least work it needs: per head and token the update s += dt x B^T and the
read-out s C (hd x N each)."""


def forward(cfg: dict, seq: int) -> float:
    d, N, W = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv_width"]
    e = cfg["ssm_expand"] * d
    nh = e // cfg["ssm_head_dim"]
    proj = 2 * d * e + 2 * d * N + d * nh + e * d
    conv = W * e
    ssm = nh * 2 * cfg["ssm_head_dim"] * N
    return 2.0 * (proj + conv + ssm)
