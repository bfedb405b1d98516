"""The embedding is a gather (no FLOPs); the tied output projection is a
(d x vocab) product per token."""


def forward(cfg: dict, seq: int) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab"]
