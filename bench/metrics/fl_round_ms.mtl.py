"""Host time per FL adaptation round of the MTL process: the window's
per-task FL chunks over the live rounds they ran (rounds after a
cluster reached its target are frozen and not counted)."""


def read(run):
    return run.host.get("fl_round_ms")
