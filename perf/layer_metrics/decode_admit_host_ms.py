"""Decode engine: the host's part of an admission that no device
program covers, mean per seated admission, in ms: the ``admit`` seconds
of ``decode_tick_seconds_total`` less the ``prefill_wait`` ones (the
stepper waiting for the prefill program's logits row), over
``decode_admissions_total``.  Pages, tables, the host arrays, the
jitted call's dispatch, the layers' report and the first token's
emission; kept by the tick's account in every run."""

from perf.harness import skeleton as sk
from perf.harness import tick_account as ta


def read(record):
    seated = sk.family_delta(record, "decode_admissions_total")
    if not seated:
        return None
    return (ta.seconds(record, ["admit"])
            - ta.seconds(record, ["prefill_wait"])) / seated * 1e3
