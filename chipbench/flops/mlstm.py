"""mLSTM layer: the projections (up, q, k, v, gates, output gate, down),
and the matrix memory in its recurrent form, the least work it needs: per
head and token, the update C += k v^T and the read-out C q (hd x hd each),
and the normaliser's n += k and n . q (hd each)."""


def forward(cfg: dict, seq: int) -> float:
    d, H = cfg["d_model"], cfg["n_heads"]
    e = 2 * d
    hd = e // H
    proj = d * e + 4 * e * e + 2 * e * H + e * d
    memory = H * (2 * hd * hd + 2 * hd)
    return 2.0 * (proj + memory)
