"""Readers: ``<reader>.py`` holds ``read(ctx, **args)``, which takes one
per-layer metric from what the traced run collected, or returns ``None``
where there is nothing to read."""
