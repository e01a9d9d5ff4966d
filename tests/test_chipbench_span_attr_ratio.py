"""The cases of chipbench/tests/test_span_attr_ratio.py, collected by the tier-1 command."""
from chipbench.tests.test_span_attr_ratio import *  # noqa: F401,F403
