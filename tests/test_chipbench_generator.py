"""The cases of chipbench/tests/test_generator.py, collected by the tier-1 command."""
from chipbench.tests.test_generator import *  # noqa: F401,F403
