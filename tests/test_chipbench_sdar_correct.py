"""The cases of chipbench/tests/test_sdar_correct.py, collected by the tier-1 command."""
from chipbench.tests.test_sdar_correct import *  # noqa: F401,F403
