"""The other cases of chipbench/tests/test_correct.py (the sound-run train
case is tests/test_chipbench_correct_train.py)."""
from chipbench.tests.test_correct import (  # noqa: F401
    test_serve_sound_run_is_correct_and_the_control_is_not,
    test_serve_token_altered_where_it_is_produced,
    test_train_half_of_the_batch_left_out,
    test_train_step_that_returns_its_state_unchanged)
