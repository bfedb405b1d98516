"""Model FLOP/s utilisation of the training step, in %: the model FLOPs
per token (``chipbench/flops``, no recomputation) times the tokens per
second of the run's steady chunks (those with no event, outside the traced
part), over the chip's bf16 peak (``chipbench/peaks.json``) times chips."""


def read(run):
    steady = [c for c in run.chunks if c["kind"] == "steady"
              and not c["traced"]]
    if not steady:
        return None
    tokens = sum(c["tokens"] for c in steady)
    seconds = sum(c["t1"] - c["t0"] for c in steady)
    return 100.0 * run.flops_per_token * tokens / seconds \
        / (run.peak_flops * run.chips)
