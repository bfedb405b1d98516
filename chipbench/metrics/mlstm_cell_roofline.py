"""Share of its roofline, in %, of the mLSTM chunkwise cell (scope
``mlstm_cell``): the least time of its model FLOPs at the bf16 peak or of
its inputs and outputs at the HBM peak, whichever is longer
(``chipbench/flops/mlstm_cell.py``), over its device self time per traced
step (``chipbench/roofline.py``)."""

from chipbench import roofline
from chipbench.flops import mlstm_cell


def read(run):
    flops, nbytes = mlstm_cell.train_step(
        run.config["arch"], run.config["global_batch"], run.seq_len)
    return roofline.share(run, "mlstm_cell", flops, nbytes)
