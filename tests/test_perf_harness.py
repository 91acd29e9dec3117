"""The benchmark's harness under the gate (ISSUE 37).

``perf/tests/`` is run by hand; what a later PR's numbers rest on runs
here, in tier-1: the judged statistic of the time to first token, the
convoy share, the idle gaps' names, the traffic files' co-prime
budgets, the load generator's token-clocked starts (PR 36's cases,
imported and not copied), the expert layer's prefill readers (PR 38's
cases, imported too), the state-space layer's readers and the new
cell's entries (PR 41's), the latent layer's readers and its cell's
entries (PR 45's), the decoder-hybrid-decoder's cell, its readers and
its rehearsal (PR 48's), the sparse latent cell's entries and its seven
readers (PR 53's), and the readers of the decode tick's own
account (PR 37) against a registry pair recorded from a session and
against spans laid over the device plane of the trace recorded on the
chip (``perf/tests/data``), and PR 51's readers of the skeleton's
scopes, of an admission's by-bucket account and of the idle under a
prefill's wait.
"""

import os
import time

import pytest

pytest.register_assert_rewrite(
    "perf.tests.test_stats", "perf.tests.test_trace",
    "perf.tests.test_traffic", "perf.tests.test_loadgen",
    "perf.tests.test_moe_prefill_readers", "perf.tests.test_ssm_readers",
    "perf.tests.test_granite_cell", "perf.tests.test_kanana_cell",
    "perf.tests.test_latent_readers", "perf.tests.test_phi4_flash_cell",
    "perf.tests.test_skeleton_readers", "perf.tests.test_glm_cell")

from perf.harness import program_spans as ps  # noqa: E402
from perf.harness import tick_account as ta  # noqa: E402
from perf.harness import trace as tr  # noqa: E402
from perf.run import load_reader  # noqa: E402
from perf.tests.test_loadgen import (  # noqa: E402,F401
    server, test_staggered_first_sends)
from perf.tests.test_moe_prefill_readers import (  # noqa: E402,F401
    test_moe_grouped_fill_is_assigned_over_computed,
    test_moe_prefill_ms_counts_the_loops_body_and_not_the_loop,
    test_moe_prefill_ms_reads_nothing_without_a_trace_or_the_layer)
from perf.tests import test_granite_cell as _granite_cell  # noqa: E402
from perf.tests import test_kanana_cell as _kanana_cell  # noqa: E402
from perf.tests import test_latent_readers as _latent_readers  # noqa: E402
from perf.tests import test_glm_cell as _glm_cell  # noqa: E402
from perf.tests import test_phi4_flash_cell as _phi4_cell  # noqa: E402
from perf.tests.test_granite_cell import (  # noqa: E402,F401
    test_correct_holds_the_attention_layers_and_the_state,
    test_the_configuration_is_the_catalogs_row_uncut,
    test_the_longest_request_fits_the_rows_a_sequence_holds,
    test_the_traffic_is_the_issues_letter_for_letter)
from perf.tests.test_kanana_cell import (  # noqa: E402,F401
    test_correct_holds_every_ablation_and_both_precisions,
    test_every_catalog_key_is_uncut_but_the_three_in_reduced,
    test_the_long_prompts_are_spread_and_the_longest_request_fits)
from perf.tests.test_skeleton_readers import (  # noqa: E402,F401
    test_a_parents_record_reads_nothing,
    test_a_program_is_split_by_the_skeletons_parts,
    test_a_rehearsals_trace_is_split_by_the_events_own_module,
    test_the_admissions_account_by_bucket,
    test_the_gpt2_cell_rehearses_traced_and_reads_what_it_lists,
    test_the_idle_under_a_prefill_is_split_at_its_wait)
from perf.tests.test_ssm_readers import (  # noqa: E402,F401
    test_a_program_without_the_scopes_reads_nothing,
    test_sizes_and_the_algorithms_counts,
    test_the_four_readers_arithmetic)
from perf.tests.test_stats import (  # noqa: E402,F401
    test_follower_share_counts_sends_in_a_convoy,
    test_interquartile_mean_does_not_sit_on_a_gap,
    test_interquartile_mean_ignores_a_stall_in_one_percent,
    test_interquartile_mean_is_the_mean_of_a_symmetric_sample)
from perf.tests.test_trace import (  # noqa: E402,F401
    test_idle_gaps_take_the_programs_own_spans)
from perf.tests.test_traffic import test_generate_traffic  # noqa: E402,F401

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.decode.session import (  # noqa: E402
    PHASE_SPANS, DecodeRequest, DecodeSession)
from tests.test_tick_account import PhasedLM  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perf", "tests", "data")

ACCOUNT_READERS = [
    "decode_tick_ms", "decode_tick_plain_ms", "decode_tick_admit_share",
    "decode_admissions_per_admitting_tick", "decode_admit_stall_share",
    "decode_tick_exposed_host_ms", "decode_step_resident_share",
    "decode_slow_ticks"]
IDLE_READERS = ["gen_idle_ids_arrival_share", "gen_idle_dispatch_share",
                "gen_idle_seat_share"]


def _as_left_with(bench, cells, metrics):
    """``BENCHMARK.json`` as the PR that brought its first ``cells``
    cells and ``metrics`` per-layer metrics left it: what later PRs
    appended (cells, metrics, the cells' names at the end of lists)
    taken off.  A later PR only appends, so this is that PR's file."""
    gone = {w["name"] for w in bench["workloads"][cells:]}

    def without(entry):
        if "workloads" not in entry:
            return entry
        return {**entry, "workloads": [w for w in entry["workloads"]
                                       if w not in gone]}

    return {**bench, "workloads": bench["workloads"][:cells],
            "end_to_end": [without(m) for m in bench["end_to_end"]],
            "per_layer": [without(m) for m in bench["per_layer"][:metrics]]}


def test_the_cell_is_appended_where_it_reports(monkeypatch):
    """PR 41's case (it holds its cell to be the LAST of 8, which the
    next appended cell ends; a PR that adds a cell edits no file under
    ``perf/``) on the benchmark as PR 41 left it."""
    monkeypatch.setattr(_granite_cell, "BENCH",
                        _as_left_with(_granite_cell.BENCH, 8, 69))
    _granite_cell.test_the_cell_is_appended_where_it_reports()


_CASE_ID = lambda f: f.__module__.rsplit(".", 1)[1] + "." + f.__name__  # noqa: E731,E501


# PR 45's cases whose names PR 41's already have in this module; the one
# that holds its cell to be the LAST of 9 sees the benchmark as PR 45
# left it
@pytest.mark.parametrize("case", [
    _kanana_cell.test_the_cell_is_appended_where_it_reports,
    _kanana_cell.test_the_traffic_is_the_issues_letter_for_letter,
    _latent_readers.test_a_program_without_the_scopes_reads_nothing,
    _latent_readers.test_sizes_and_the_algorithms_counts,
    _latent_readers.test_the_four_readers_arithmetic], ids=_CASE_ID)
def test_the_latent_cell_and_its_readers(case, monkeypatch):
    monkeypatch.setattr(_kanana_cell, "BENCH",
                        _as_left_with(_kanana_cell.BENCH, 9, 73))
    case()


# PR 48's: the decoder-hybrid-decoder's cell (the last of 10 today; the
# case that holds its five metrics to be the last of 78 sees the
# benchmark as PR 48 left it)
@pytest.mark.parametrize("case", [
    _phi4_cell.test_the_traffic_is_the_issues_letter_for_letter,
    _phi4_cell.test_the_long_prompts_are_spread_and_the_budgets_dealt_alike,
    _phi4_cell.test_the_configuration_is_the_catalogs_row_uncut,
    _phi4_cell.test_the_cell_is_appended_where_it_reports,
    _phi4_cell.test_correct_holds_every_ablation_and_the_state,
    _phi4_cell.test_sizes_and_the_algorithms_counts,
    _phi4_cell.test_the_readers_arithmetic,
    _phi4_cell.test_a_program_without_the_scopes_or_the_counters_reads_nothing,  # noqa: E501
    _phi4_cell.test_the_cell_rehearses_traced_and_reads_every_new_metric],
    ids=_CASE_ID)
def test_the_decoder_hybrid_decoder_cell_and_its_readers(case, monkeypatch):
    if case is _phi4_cell.test_the_cell_is_appended_where_it_reports:
        monkeypatch.setattr(_phi4_cell, "BENCH",
                            _as_left_with(_phi4_cell.BENCH, 10, 78))
    case()


# PR 53's: the sparse latent cell (the last of 11 today), its entries
# and its seven readers; its rehearsal runs from ``tests/test_glm_cell.py``,
# a file of its own, so that another worker takes it
@pytest.mark.parametrize("case", [
    _glm_cell.test_the_traffic_is_the_issues_letter_for_letter,
    _glm_cell.test_every_context_selects_and_the_longest_sequence_fits,
    _glm_cell.test_every_catalog_key_is_uncut_but_the_three_in_reduced,
    _glm_cell.test_the_cell_is_appended_where_it_reports,
    _glm_cell.test_every_listed_reader_loads,
    _glm_cell.test_correct_holds_every_ablation_and_the_precisions,
    _glm_cell.test_sizes_and_the_algorithms_counts,
    _glm_cell.test_the_seven_readers_arithmetic,
    _glm_cell.test_a_program_without_the_scopes_or_the_counters_reads_nothing],  # noqa: E501
    ids=_CASE_ID)
def test_the_sparse_latent_cell_and_its_readers(case):
    case()


def test_the_harness_names_the_phases_as_the_session_does():
    """A prefill's wait (PR 51) is the session's and
    ``perf/harness/skeleton.py``'s: ``tick_account.reconcile`` keeps the
    table of labels it lays beside the spans, which only a ``benchmark``
    PR edits, and a nested label changes no sum it takes."""
    from perf.harness.skeleton import PREFILL_PHASES

    assert {**ta.PHASE_SPANS, **PREFILL_PHASES} == PHASE_SPANS
    assert not set(ta.PHASE_SPANS) & set(PREFILL_PHASES)
    assert set(ta.IN_TICK) | {"between", "prefill", "first_token"} | set(
        PREFILL_PHASES) == set(PHASE_SPANS) | {"other"}


# -- a registry pair recorded from a session ---------------------------------


@pytest.fixture(scope="module")
def recorded():
    """40 ticks of the recording model under a queue of short requests,
    as a run's record: the registry before and after, and the ring's
    spans in the shape ``trace.load`` gives (ns), inside a window span,
    beside a device that runs while a step is in flight."""
    lm = PhasedLM(num_pages=256, page_size=4, pages_per_seq=16)
    lm.collect_s = 0.0005
    prefill = lm.prefill

    def slow_prefill(prompt, pages, cached_len=0):
        time.sleep(0.001)       # an admitting tick is the longer one
        return prefill(prompt, pages, cached_len)

    lm.prefill = slow_prefill
    sess = DecodeSession(lm, max_slots=3)
    for i in range(40):
        sess.submit(DecodeRequest([2, 5, 7 + i % 3], max_new_tokens=5))
    sess.step()                     # the window opens on a running engine
    with obs.recording() as ring:
        before = obs.snapshot()
        for _ in range(40):
            sess.step()
        after = obs.snapshot()
        events = ring.events()
    ring.clear()
    host = [("python3", e["name"], e["ts"] * 1e3, e["dur"] * 1e3)
            for e in events]
    lo = min(s for _, _, s, _ in host) - 1e3
    hi = max(s + d for _, _, s, d in host) + 1e3
    host.append(("python3", tr.WINDOW_SPAN, lo, hi - lo))
    # the device runs from a dispatch's end to the next collect's end
    ends = sorted(s + d for _, n, s, d in host if n == "decode.dispatch")
    lands = sorted(s + d for _, n, s, d in host
                   if n == "decode.logits_to_host")
    ops = [("fusion.1", a, b - a - 2e5, {})
           for a, b in zip(ends, [x for x in lands if x > ends[0]])]
    return {"registry": {"before": before, "after": after},
            "trace": {"host": host, "devices": {"/device:TPU:0": ops}},
            "traffic": {"gen_slots": 3}}


def _ring_seconds(record, name):
    return sum(d for _, n, _, d in record["trace"]["host"] if n == name) / 1e9


def test_label_filtered_deltas(recorded):
    reg = recorded["registry"]
    fam = reg["after"]["decode_tick_seconds_total"]["values"]
    assert {v["labels"]["admitting"] for v in fam} == {"0", "1"}
    assert ta.delta(recorded, "decode_ticks_total") == 40
    assert (ta.delta(recorded, "decode_ticks_total", admitting="0")
            + ta.delta(recorded, "decode_ticks_total", admitting="1")) == 40
    # a label that no child has, a family that is not there
    assert ta.delta(recorded, "decode_ticks_total", admitting="2") == 0
    assert ta.delta(recorded, "decode_no_such_total") == 0
    assert ta.total({}, "decode_ticks_total") == 0.0
    for label, name in PHASE_SPANS.items():
        if label == "between":
            continue        # the one open when the window opened
        assert ta.seconds(recorded, [label]) == pytest.approx(
            _ring_seconds(recorded, name), rel=1e-6), label


@pytest.mark.parametrize("metric", ACCOUNT_READERS)
def test_account_readers_on_a_recorded_pair(recorded, metric):
    got = load_reader(metric)(recorded)
    tick_s = _ring_seconds(recorded, "decode.tick")
    admit_s = _ring_seconds(recorded, "decode.admit")
    n_admit = sum(1 for _, n, _, _ in recorded["trace"]["host"]
                  if n == "decode.admit")
    admitting = ta.delta(recorded, "decode_ticks_total", admitting="1")
    want = {
        "decode_tick_ms": tick_s / 40 * 1e3,
        "decode_tick_admit_share": 100.0 * admit_s / tick_s,
        "decode_admissions_per_admitting_tick": n_admit / admitting,
        "decode_slow_ticks": 0.0,
    }.get(metric)
    if want is not None:
        assert got == pytest.approx(want, rel=1e-6)
    elif metric == "decode_tick_plain_ms":
        assert 0 < got < load_reader("decode_tick_ms")(recorded)
    elif metric == "decode_tick_exposed_host_ms":
        # host work outside the collect, the admissions and the delivery
        assert 0 < got < (tick_s - admit_s) / 40 * 1e3 + 1.0
    elif metric == "decode_admit_stall_share":
        # at most two of three slots stand still under an admission
        assert 0 < got < 100.0 * admit_s / tick_s
    else:
        assert metric == "decode_step_resident_share" and 0 <= got < 100


@pytest.mark.parametrize("metric", ACCOUNT_READERS + IDLE_READERS
                         + ["serve_submit_lag_ms"])
def test_new_readers_return_none_on_a_record_of_the_parent(metric):
    """The driver lays this PR's benchmark files over the parent's
    checkout: there the program keeps no account, and a reader returns
    None (the line leaves the metric out) and does not raise."""
    read = load_reader(metric)
    registry = {"before": {"decode_steps_total": {"values": [
                    {"labels": {}, "value": 1}]}},
                "after": {"decode_steps_total": {"values": [
                    {"labels": {}, "value": 9}]},
                          "decode_step_inputs_total": {"values": [
                    {"labels": {"source": "resident"}, "value": 7}]}}}
    traced = {"trace": {"host": [("python3", tr.WINDOW_SPAN, 1000.0, 9000.0),
                                 ("python3", "perf.engine_step", 2000.0,
                                  1000.0)],
                        "devices": {"/device:TPU:0": [
                            ("fusion.1", 1000.0, 2000.0, {})]}},
              "registry": registry, "span_seconds": {},
              "traffic": {"gen_slots": 16}}
    assert read(traced) is None
    assert read({"trace": None, "registry": None}) is None


def test_the_account_reconciles_with_the_spans(recorded, capsys):
    laid = ta.reconcile(recorded)
    for label, (counted, spanned) in laid["phases"].items():
        if label == "between":
            assert counted >= spanned       # the one open at the start
        else:
            assert counted == pytest.approx(spanned, rel=1e-6, abs=1e-9), \
                label
    assert laid["counted_s"] == pytest.approx(
        sum(laid["phases"][p][0] for p in ta.IN_TICK + ("between",)))
    # no idle wait in it: ticks and the time between them tile the window
    assert laid["spanned_s"] == pytest.approx(
        laid["window_less_idle_wait_s"], rel=0.02)
    idle = laid["idle"]
    assert idle["tick_share"] == pytest.approx(
        load_reader("gen_idle_tick_share")(recorded))
    assert idle["tick_parts"]["ids_arrival"] == pytest.approx(
        load_reader("gen_idle_ids_arrival_share")(recorded))
    assert sum(idle["tick_parts"].values()) == pytest.approx(
        idle["tick_share"], abs=1e-6)
    assert sum(idle["prefill_parts"].values()) == pytest.approx(
        idle["prefill_share"], abs=1e-6)
    # the reader that prints it in a traced run's log
    assert load_reader("decode_tick_ms")(recorded) > 0
    assert "tick account beside the spans: {" in capsys.readouterr().out
    assert ta.reconcile({"trace": None, "registry": None}) is None
    assert ta.reconcile(dict(recorded, trace=None)) is None


# -- spans laid over the device plane recorded on the chip -------------------


@pytest.fixture(scope="module")
def chip_plane():
    """The first device's ops of ``small_tpu_4.xplane.pb`` (recorded on
    four chips at PR 23: three short steps and long gaps between them)
    with a stepper's spans laid over its window: ticks of 20 ms, back to
    back, each a collect, a decide, an admission with its prefill and
    first token in every third, a step and a delivery."""
    t = tr.load(os.path.join(DATA, "small_tpu_4.xplane.pb"))
    lo, hi = tr.window(t)
    host = [("python3", tr.WINDOW_SPAN, lo, hi - lo)]
    ms, at, k = 1e6, lo + 3e5, 0
    while at + 20 * ms < hi:
        def put(name, start_ms, dur_ms):
            host.append(("python3", name, at + start_ms * ms, dur_ms * ms))

        put("decode.tick", 0.0, 19.8)
        put("decode.logits_to_host", 0.1, 9.0)
        put("decode.sample", 9.2, 0.5)
        put("decode.sweep", 9.8, 0.2)
        if k % 3 == 0:
            put("decode.admit", 10.1, 5.0)
            put("decode.prefill", 10.5, 4.0)
            put("decode.first_token", 14.6, 0.4)
        put("decode.cow", 15.2, 0.3)
        put("decode.step", 15.6, 2.0)
        put("decode.upload", 15.7, 0.8)
        put("decode.dispatch", 16.5, 1.0)
        put("decode.deliver", 17.8, 1.5)
        put("decode.between", 19.8, 0.2)
        at, k = at + 20 * ms, k + 1
    plane = sorted(t["devices"])[0]
    return {"trace": {"host": host,
                      "devices": {plane: t["devices"][plane]}},
            "registry": None}


def test_the_idle_shares_split_the_two_lumps(chip_plane):
    read = {m: load_reader(m)(chip_plane) for m in IDLE_READERS + [
        "gen_idle_tick_share", "gen_idle_prefill_share"]}
    assert all(v is not None and v > 0 for v in read.values()), read
    trace = chip_plane["trace"]
    window = tr.window(trace)
    span_s = (window[1] - window[0]) / 1e9

    def idle(names, outside=()):
        return 100.0 * ps.idle_under(trace, names, outside) / span_s

    residual = sum(idle([n]) for n in ta.IDLE_RESIDUAL) + idle(
        ["decode.tick"], ta.TICK_CHILDREN)
    assert (read["gen_idle_ids_arrival_share"]
            + read["gen_idle_dispatch_share"] + residual) == pytest.approx(
                read["gen_idle_tick_share"], abs=1e-6)
    assert (read["gen_idle_seat_share"] + idle(["decode.prefill"])
            ) == pytest.approx(read["gen_idle_prefill_share"], abs=1e-6)
    # the device is idle nearly all of this window (it runs 48 us of
    # 235 ms): a share is its spans' own part of the window, nearly
    ticks = ps.count(trace, "decode.tick")
    admits = ps.count(trace, "decode.admit")
    per_ms = 100.0 / (span_s * 1e3)
    assert read["gen_idle_ids_arrival_share"] == pytest.approx(
        ticks * 9.0 * per_ms, abs=0.05)
    assert read["gen_idle_dispatch_share"] == pytest.approx(
        ticks * 2.0 * per_ms, abs=0.05)
    assert read["gen_idle_seat_share"] == pytest.approx(
        admits * 1.0 * per_ms, abs=0.05)


def test_a_step_run_whole_keeps_its_collect_out_of_the_dispatch_share():
    T = "python3"
    trace = {"host": [(T, tr.WINDOW_SPAN, 1000.0, 10000.0),
                      (T, "decode.tick", 2000.0, 8000.0),
                      (T, "decode.step", 3000.0, 5000.0),
                      (T, "decode.upload", 3000.0, 1000.0),
                      (T, "decode.dispatch", 4000.0, 1000.0),
                      (T, "decode.logits_to_host", 5000.0, 3000.0)],
             "devices": {"/device:TPU:0": [("fusion.1", 5500.0, 2000.0, {})]}}
    record = {"trace": trace}
    assert load_reader("gen_idle_dispatch_share")(record) == \
        pytest.approx(20.0)
    assert load_reader("gen_idle_ids_arrival_share")(record) == \
        pytest.approx(10.0)


def test_submit_lag_reads_the_histograms_mean():
    hist = {"values": [{"labels": {}, "count": 4, "sum": 0.002}]}
    later = {"values": [{"labels": {}, "count": 14, "sum": 0.006}]}
    name = "serving_generate_submit_lag_seconds"
    record = {"registry": {"before": {name: hist}, "after": {name: later}}}
    assert load_reader("serve_submit_lag_ms")(record) == pytest.approx(0.4)
