"""Decode engine: rows the window's admitting ticks would have
computed had each laid its prompts end to end through the model's own
bucket ladder (where that is fewer), over the bucket rows they did
compute: ``decode_admit_tick_rows_total{kind="packed"}`` over
``{kind="run"}``, in %.  The one counterfactual in the account: 100
less this is the most that packing a tick's prompts could save."""

from perf.harness import skeleton as sk
from perf.harness import tick_account as ta


def read(record):
    name = "decode_admit_tick_rows_total"
    return ta.share(sk.family_delta(record, name, kind="packed"),
                    sk.family_delta(record, name, kind="run"))
