"""Zamba2's hybrid layer at the chip's share (``reference/zamba2_hybrid.py``),
counted at every occurrence, not once per shared set: q/k/v from the 2d-wide
concat and o back to d, causal attention over the keys each query needs (on
average (S+1)/2), the gated MLP (gate and up, down) with its LoRA adapter
(rank 128), the projection W_lin, and its Mamba2 layer (``mamba2.py``)."""

from chipbench.flops import mamba2
from chipbench.reference.zamba2_hybrid import RANK, mlp_columns


def forward(cfg: dict, seq: int) -> float:
    d, H, KV, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    f = mlp_columns(cfg)
    keys = (seq + 1) / 2
    proj = 2 * d * (H + 2 * KV) * hd + H * hd * d
    attn = 2 * H * hd * keys
    mlp = d * 2 * f + f * d + d * RANK + RANK * 2 * f
    return 2.0 * (proj + attn + mlp + d * d) + mamba2.forward(cfg, seq)
