"""Run by hand (tier-1 is ``tests/`` and does not collect this):

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
