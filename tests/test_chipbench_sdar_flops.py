"""The cases of chipbench/tests/test_sdar_flops.py, collected by the tier-1 command."""
from chipbench.tests.test_sdar_flops import *  # noqa: F401,F403
