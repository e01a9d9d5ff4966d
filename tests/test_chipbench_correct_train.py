"""chipbench/tests/test_correct.py's sound-run train case, a module of its
own: it is the longest single case, and --dist loadfile gives a worker a
whole module."""
from chipbench.tests.test_correct import (  # noqa: F401
    test_train_sound_run_is_correct_control_and_half_batch_are_not)
