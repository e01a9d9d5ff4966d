"""The cases of chipbench/tests/test_manifest.py, collected by the tier-1 command."""
from chipbench.tests.test_manifest import *  # noqa: F401,F403
