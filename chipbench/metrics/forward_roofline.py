"""Share of its roofline that the forward reaches: the least time of the
operations and bytes (``work/<family>.py``) of every forward that ran in
the traced window, over their device time. On the subgraph path the work
is that of each served closure, real and unpadded."""


def read(run):
    return run.roofline(run.forward_pattern, run.forward_work())
