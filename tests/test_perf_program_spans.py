"""``perf/tests/test_program_spans.py`` under the gate (ISSUE 44): the
span reductions every ``program_span`` metric reads, imported and not
copied.  A file of its own: ``test_new_readers_return_none_on_a_record_
of_the_parent`` is also the name of a test of ``test_perf_harness.py``.
"""

import pytest

pytest.register_assert_rewrite("perf.tests.test_program_spans")

from perf.tests.test_program_spans import (  # noqa: E402,F401
    test_count_and_mean_take_spans_that_start_in_the_window,
    test_idle_share_tells_no_spans_from_no_idle_wait,
    test_idle_under_takes_the_part_of_a_gap_inside_the_span,
    test_new_readers_return_none_on_a_record_of_the_parent,
    test_record_level_readers_return_none_without_the_programs_spans,
    test_self_time_subtracts_nested_children_inside_the_window,
    test_self_time_takes_children_by_name_across_threads)
