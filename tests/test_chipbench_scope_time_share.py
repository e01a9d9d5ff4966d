"""The cases of chipbench/tests/test_scope_time_share.py, collected by the tier-1 command."""
from chipbench.tests.test_scope_time_share import *  # noqa: F401,F403
