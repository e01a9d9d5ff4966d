"""The cases of chipbench/tests/test_tracelib.py, collected by the tier-1 command."""
from chipbench.tests.test_tracelib import *  # noqa: F401,F403
