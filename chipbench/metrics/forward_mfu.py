"""Model operations of the forwards that ran in the traced window over the
window times the chip's bf16 peak: the whole forward's share of the
chip."""


def read(run):
    return run.mfu(run.forward_pattern,
                   [ops for ops, _ in run.forward_work()])
