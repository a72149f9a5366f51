"""A number the worker's last report brought back under ``key`` — a counter
of the program's that the traffic kind averaged over the window: at the
report's top level where the kind reads it in every run
(``kinds/bd_train_loop.py``'s ``moe_rows_held``), else among the traced run's
``counters`` (``kinds/train_loop.py::_Profiler``: whatever
``ShardedPretrainer.moe_stats`` held at the window's reports).  Nothing where
the run has none."""


def read(ctx, key: str):
    value = ctx.measured.get(key)
    if value is None:
        value = ((ctx.measured.get("trace") or {}).get("counters")
                 or {}).get(key)
    return None if value is None else float(value)
