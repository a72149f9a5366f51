"""Model families: ``<family>.py`` holds what the benchmark knows of one
architecture, found by the ``family`` of a configuration file:

``shape(config, chips)``         the sizes ``flops.py`` counts from
``model_config(config, chips)``  the program's own dataclass, filled from the
                                 published keys: the one place where the
                                 benchmark's key names meet the program's
``logits(params, ids, config)``  the plain float32 forward that
                                 ``reference.py`` differentiates

A later PR adds an architecture as one module here; nothing lists them.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict


def of(config: Dict[str, Any]):
    return importlib.import_module(
        f"perfbench.harness.families.{config['family']}")


def published(config: Dict[str, Any], chips: int, key: str):
    """A key of the configuration file as it is run on ``chips`` chips: the
    depth is cut per chip count (``cut_by_chips``), everything else is the
    published value."""
    return config.get("cut_by_chips", {}).get(str(chips), {}).get(
        key, config[key])
