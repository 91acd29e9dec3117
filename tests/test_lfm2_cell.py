"""The ``lfm2-8b-a1b-generate-rag`` cell's cases
(``perf/tests/test_lfm2_cell.py``, imported and not copied): its traffic,
configuration and list entries, its five readers, and the cell rehearsed
end to end on the CPU, traced and untraced.  In a file of its own so
that the suite's workers share the cells' rehearsals.  The case that
holds its five metrics to list that cell ALONE sees the benchmark as
PR 59 left it (PR 62 appended its cell to two of them; a PR that adds a
cell edits no file under ``perf/``)."""

import pytest

pytest.register_assert_rewrite("perf.tests.test_lfm2_cell")

from perf.tests.test_lfm2_cell import (  # noqa: E402,F401
    test_a_program_without_the_scopes_or_the_counters_reads_nothing,
    test_correct_holds_every_ablation_and_the_precisions,
    test_every_catalog_key_is_uncut_but_the_depth,
    test_every_listed_reader_loads,
    test_the_attention_layers_are_counted_from_layer_types,
    test_the_cell_rehearses_traced_and_reads_what_it_lists,
    test_the_cell_rehearses_untraced,
    test_the_five_readers_arithmetic,
    test_the_longest_sequence_fits_and_the_ramp_clears_the_first_prefills,
    test_the_traffic_is_the_issues_letter_for_letter)
from perf.tests import test_lfm2_cell as _cell  # noqa: E402
from tests.test_perf_harness import _as_left_with  # noqa: E402


def test_the_cell_is_appended_where_it_reports(monkeypatch):
    monkeypatch.setattr(_cell, "BENCH", _as_left_with(_cell.BENCH, 13, 107))
    _cell.test_the_cell_is_appended_where_it_reports()
