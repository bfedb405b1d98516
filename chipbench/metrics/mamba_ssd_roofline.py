"""Share of its roofline, in %, of the Mamba2 SSD (scope ``mamba_ssd``): the
least time of its model FLOPs at the bf16 peak or of its inputs and
outputs at the HBM peak, whichever is longer
(``chipbench/flops/mamba_ssd.py``), over its device self time per traced
step (``chipbench/roofline.py``)."""

from chipbench import roofline
from chipbench.flops import mamba_ssd


def read(run):
    flops, nbytes = mamba_ssd.train_step(
        run.config["arch"], run.config["global_batch"], run.seq_len)
    return roofline.share(run, "mamba_ssd", flops, nbytes)
