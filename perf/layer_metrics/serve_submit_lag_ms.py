"""Serving front: ``serving_generate_submit_lag_seconds`` sum/count
delta over the window: from a ``/generate`` handler's entry to the
decode engine holding the request (body parse, tenant admission, the
submit), in ms.  What ``serve_front_ms`` takes by subtracting two
means, measured where it happens."""

from perf.harness.readers import registry_mean_ms


def read(record):
    return registry_mean_ms(record, "serving_generate_submit_lag_seconds")
