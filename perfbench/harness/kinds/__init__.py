"""Traffic kinds: ``<kind>.py``, found by the ``kind`` of the cell's traffic
file, holds everything that belongs to one kind of traffic.

``run(cell, seed=, seconds=, trace=, t_start=, trace_dir=)``, driver side:
start the system under test as its users do, offer the traffic, and return
the measurements ``m`` it brought back.  Then, over that ``m``:
``end_to_end(cell, m, peak)``, the values of the end-to-end metrics by name;
``verdict(cell, m)``, the kind's conditions of ``correct`` by name;
``detail(m)``, what a person wants beside the metrics.

What every kind's ``m`` holds, because the driver and the readers take it
from there: ``device`` (``platform``, ``kind``, ``count``), ``memory`` (one
``memory_stats()`` per device), ``steps`` and ``failed`` (operations of the
window: ``attempted`` and ``failed`` of the line), ``compiled_in_window``,
``build_events``, ``spans_ms`` and, in a traced run, ``trace``
(``file``: the reduced trace, ``steps``: the operations it covers).

A later PR adds a kind — serving, a checkpointing loop — as one module here
with the metrics it reports; nothing lists them.
"""
