"""Serving front: ``serving_generate_first_write_lag_seconds`` sum/count
delta over the window: from the stepper emitting a request's first token
to the handler thread having written it on the socket."""

from perf.harness.readers import registry_mean_ms


def read(record):
    return registry_mean_ms(record,
                            "serving_generate_first_write_lag_seconds")
