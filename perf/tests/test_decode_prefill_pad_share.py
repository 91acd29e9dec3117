"""``decode_prefill_pad_share`` over registry snapshots: pad rows over
computed rows in the window, and nothing where the program has no such
counters (the parent of the PR that added them)."""

from perf.run import load_reader


def _read(record):
    return load_reader("decode_prefill_pad_share")(record)


def _snap(real, padded):
    return {"decode_prefill_tokens_total":
            {"values": [{"labels": {}, "value": real}]},
            "decode_prefill_padded_tokens_total":
            {"values": [{"labels": {}, "value": padded}]}}


def test_pad_share_is_the_windows_delta():
    record = {"registry": {"before": _snap(100, 128),
                           "after": _snap(100 + 70 + 64, 128 + 128 + 64)}}
    assert _read(record) == 100.0 * 58 / 192


def test_no_padding_reads_zero_and_no_counter_reads_nothing():
    assert _read({"registry": {"before": _snap(0, 0),
                               "after": _snap(64, 64)}}) == 0.0
    assert _read({"registry": {"before": {}, "after": {}}}) is None
    assert _read({}) is None
