"""Host time per meta round of the MAML stage: the window's meta chunks
(from each chunk's dispatch, after the previous boundary, to its losses
on the host), over the meta rounds they ran."""


def read(run):
    return run.host.get("meta_round_ms")
