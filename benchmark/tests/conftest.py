"""``python -m pytest benchmark/tests -q`` from the root of the checkout.
Everything here runs on the CPU; no test reports a device number."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"
