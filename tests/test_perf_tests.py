"""The rest of the benchmark's own quick tests under the gate (ISSUE 44).

``tests/test_perf_harness.py`` brought 22 of ``perf/tests``' cases into
tier-1 (ISSUE 37); these are the others that run no cell:
``BENCHMARK.json``'s entries against their files, the pad share's
reader, the load generator, the expert layer's step readers, the
statistics and the trace reductions, imported and not copied.
``perf/tests/test_program_spans.py`` has a file of its own beside this
one (a function of its shares a name with one of
``test_perf_harness.py``), and ``test_rehearse.py`` stays out: it runs
the cells.
"""

import pytest

pytest.register_assert_rewrite(
    "perf.tests.test_benchmark_json",
    "perf.tests.test_decode_prefill_pad_share", "perf.tests.test_loadgen",
    "perf.tests.test_moe_readers", "perf.tests.test_stats",
    "perf.tests.test_trace")

from perf.tests.test_benchmark_json import (  # noqa: E402,F401
    bench,
    test_every_entry_resolves_to_its_files,
    test_keys_and_limits,
    test_names_units_and_lines,
    test_one_reader_serves_several_names)
from perf.tests.test_decode_prefill_pad_share import (  # noqa: E402,F401
    test_no_padding_reads_zero_and_no_counter_reads_nothing,
    test_pad_share_is_the_windows_delta)
from perf.tests.test_loadgen import (  # noqa: E402,F401
    server,
    test_closed_loop_counts_attempted_and_failed,
    test_every_seed_deals_the_same_requests_in_the_same_order,
    test_spec_carries_the_traffic_parameters)
from perf.tests.test_moe_readers import (  # noqa: E402,F401
    test_a_program_without_the_layer_reads_nothing,
    test_events_count_only_inside_their_programs_runs,
    test_instructions_by_scope_and_by_name,
    test_module_runs_of_a_recorded_chip_trace,
    test_the_readers_arithmetic)
from perf.tests.test_stats import (  # noqa: E402,F401
    test_lm_flops,
    test_percentile_interpolates_like_numpy,
    test_quartile_spread_is_the_contracts,
    test_rate_and_window,
    test_registry_deltas_take_sums_and_counts)
from perf.tests.test_trace import (  # noqa: E402,F401
    small,
    test_busy_is_the_union_clipped_to_the_window,
    test_categories,
    test_exposed_collective_time,
    test_hlo_text_names_kernels_and_heavy_fusions,
    test_idle_gaps_are_named_after_host_spans,
    test_interval_arithmetic,
    test_kernel_sum_takes_the_named_instructions,
    test_load_keeps_ops_lines_and_host_spans,
    test_recorded_chip_trace,
    test_self_time_subtracts_nested_ops,
    test_window_is_the_host_span)
