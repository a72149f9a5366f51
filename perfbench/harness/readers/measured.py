"""A number the worker's last report brought back under ``key`` — a counter
of the program's that the traffic kind averaged over the window.  Nothing
where the run has none."""


def read(ctx, key: str):
    value = ctx.measured.get(key)
    return None if value is None else float(value)
