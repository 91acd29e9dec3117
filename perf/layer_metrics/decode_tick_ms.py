"""Decode engine: seconds of one tick of the stepper, mean over the
window's ticks, in ms: ``decode_tick_seconds_total`` over every phase
inside the tick (``between`` apart) / ``decode_ticks_total``.  The
program's own account (PR 37), kept in every run, traced or not.  In a
traced run the account is also laid beside the window's spans and the
three reconciliations go to the log (``tick_account.reconcile``)."""

import json

from perf.harness import tick_account as ta
from perf.harness.runtime import say


def read(record):
    laid = ta.reconcile(record)
    if laid is not None:
        say("tick account beside the spans: " + json.dumps(laid))
    return ta.ms_per_tick(record, ta.IN_TICK)
