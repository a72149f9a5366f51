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
