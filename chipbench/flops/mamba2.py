"""Zamba2's Mamba2 layer at the chip's share (``reference/mamba2.py``): the
projections (z, x, B, C, dt, out), the depthwise convolution over x, B and
C, and the state space recurrence in its recurrent form, the least work it
needs: per head and token the update s += dt x B^T and the read-out s C
(hd x N each)."""

from chipbench.reference.mamba2 import share


def forward(cfg: dict, seq: int) -> float:
    d, N, W = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv_width"]
    s = share(cfg)
    e, nh, bc = s["e"], s["heads"], s["groups"] * N
    proj = 2 * d * e + 2 * d * bc + d * nh + e * d
    conv = W * (e + 2 * bc)
    ssm = nh * 2 * cfg["ssm_head_dim"] * N
    return 2.0 * (proj + conv + ssm)
