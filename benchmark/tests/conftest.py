"""Run by hand: ``JAX_PLATFORMS=cpu pytest benchmark/tests`` from the root of
the repo. Not part of the tier-1 command."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
