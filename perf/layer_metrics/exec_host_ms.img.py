"""Executor: the runner's own span round each ``exe.run`` call (which
returns before the device is done), mean per step, ResNet cells."""

from perf.harness.readers import span_mean_ms


def read(record):
    return span_mean_ms(record, "perf.exe_run")
