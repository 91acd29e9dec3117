"""The yardstick: everything a later PR may read and may not change.

``peaks``    published peaks of the chips this benchmark may run on
``flops``    operations and bytes of the models and kernels, from shapes
``stats``    percentiles, quartile spread, the window timer
``registry`` deltas of the program's observability registry over a window
``trace``    reduction of a profiler trace to busy time, op sums and gaps
``loadgen``  the load generator (a child process that never imports jax)
"""
