"""The ``nemotron-3-nano-30b-a3b-generate-subagent`` cell's cases
(``perf/tests/test_nemotron_cell.py``, imported and not copied): its
traffic, configuration and list entries, what ``correct`` holds, its
three readers, and the cell rehearsed end to end on the CPU, traced and
untraced.  In a file of its own so that the suite's workers share the
cells' rehearsals."""

import pytest

pytest.register_assert_rewrite("perf.tests.test_nemotron_cell")

from perf.tests.test_nemotron_cell import (  # noqa: E402,F401
    test_a_program_without_the_scopes_or_the_counters_reads_nothing,
    test_correct_holds_every_ablation_and_the_precision_below,
    test_every_catalog_key_is_uncut_but_the_two_in_reduced,
    test_every_listed_reader_loads,
    test_sizes_and_the_algorithms_counts,
    test_the_cell_is_appended_where_it_reports,
    test_the_cell_rehearses_traced_and_reads_what_it_lists,
    test_the_cell_rehearses_untraced,
    test_the_longest_sequence_fits_and_no_prompt_is_over_the_top_bucket,
    test_the_three_readers_arithmetic,
    test_the_traffic_is_the_issues_letter_for_letter)
