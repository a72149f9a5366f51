"""The benchmark's own tests: ``python3 -m pytest perfbench/tests -q`` from the
root of the checkout.  CPU only — they check arithmetic, the manifest and that
a run without a chip prints nothing; no device number comes from here."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = os.pathsep.join(
    p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
os.environ["JAX_PLATFORMS"] = "cpu"
# Pallas kernels interpreted because this file asks for it, as
# ``tests/conftest.py`` does through ``force_cpu_platform``: without it a
# kernel lowers through Mosaic and fails on the CPU backend, by design
# (``test_reference_granite.py`` since PR 43).  Inherited by the workers the
# rehearsals start — which is why the rehearsals' windows are 4 s (2 s until
# PR 67): interpreted kernels make 2 s eight steps on a loaded machine, and a
# toy's loss is not below its first steps' before the twelfth.
os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
