"""One occurrence of the shared block (counted at every occurrence, not
once per weight set): the adapter (d x d), q/k/v/o, causal attention over
the keys each query needs (on average (S+1)/2 of them, at most the
window), and the SwiGLU MLP (three d x d_ff products)."""


def forward(cfg: dict, seq: int) -> float:
    d, H, KV, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                   cfg["d_ff"])
    hd = cfg["head_dim"] or d // H
    window = cfg["attn_window"] or seq
    # mean over positions t = 0..S-1 of the keys seen, min(t + 1, window)
    keys = sum(min(t + 1, window) for t in range(seq)) / seq
    proj = d * d + d * H * hd + 2 * d * KV * hd + H * hd * d
    attn = 2 * H * hd * keys
    return 2.0 * (proj + attn + 3 * d * f)
