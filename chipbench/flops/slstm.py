"""sLSTM layer: the gate projection (d x 4d), the block-diagonal recurrent
product per head (hd x 4hd), and the gated MLP (d x 2f, f x d)."""


def forward(cfg: dict, seq: int) -> float:
    d, H = cfg["d_model"], cfg["n_heads"]
    hd = d // H
    f = int(4 * d / 3 / 64) * 64 or 64
    return 2.0 * (d * 4 * d + H * hd * 4 * hd + d * 2 * f + f * d)
