"""Operations per dispatch of the serving router over the window
(``RouterMetrics``: dispatched writes plus lookups, over dispatches)."""


def read(run):
    c = run.router
    if not c or c["dispatches"] == 0:
        return None
    return (c["write_ops"] + c["read_ops"]) / c["dispatches"]
