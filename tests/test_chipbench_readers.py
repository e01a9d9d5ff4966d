"""The cases of chipbench/tests/test_readers.py, collected by the tier-1 command."""
from chipbench.tests.test_readers import *  # noqa: F401,F403
