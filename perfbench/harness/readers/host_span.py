"""Median milliseconds of one of the benchmark's host spans (``input``,
``step``, ``sync``, ``report``) over the whole window, on the host clock."""

from statistics import median


def read(ctx, span: str):
    values = ctx.measured["spans_ms"].get(span)
    return median(values) if values else None
