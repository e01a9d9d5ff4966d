"""The cases of chipbench/tests/test_reftrain.py, collected by the tier-1 command."""
from chipbench.tests.test_reftrain import *  # noqa: F401,F403
