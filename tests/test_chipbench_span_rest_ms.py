"""The cases of chipbench/tests/test_span_rest_ms.py, collected by the tier-1 command."""
from chipbench.tests.test_span_rest_ms import *  # noqa: F401,F403
