"""What a run needs from the machine it is started on: the device it
may use, the compile cache, compile events, host spans, peak memory."""

import contextlib
import time


def say(msg):
    print(f"[perf] {msg}", flush=True)


def configure_cache(rehearse):
    """JAX's persistent compilation cache at the fixed path the program
    itself uses (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``.jax_compile_cache/`` in the checkout), keeping even sub-second
    compiles: the eager ops of an un-jitted prefill compile in
    milliseconds each and there are hundreds of them.  A rehearsal
    keeps the program's own thresholds: its toy CPU programs, left in
    the checkout's cache, made ``paddle compile --smoke`` reject a
    bucket in the repo's own tests."""
    import jax

    from paddle_tpu import compile_cache

    path = compile_cache.configure()
    if not rehearse:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_device(chips, rehearse):
    """The device record of the result line.  Exits non-zero unless jax
    runs on a TPU in the peaks table with at least ``chips`` chips; a
    rehearsal takes whatever jax has and is never a result."""
    import jax

    from perf.harness.peaks import peaks

    devs = jax.devices()
    record = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"perf: rehearsal needs {chips} (virtual) "
                             f"devices, jax sees {len(devs)}")
        # the v5e's peaks stand in, so that the readers' arithmetic runs
        return record, peaks("TPU v5 lite")
    if record["platform"] != "tpu":
        raise SystemExit(f"perf: jax found no TPU (platform="
                         f"{record['platform']!r}); a run on another "
                         "backend is not a measurement")
    if len(devs) < chips:
        raise SystemExit(f"perf: the cell needs {chips} chip(s), jax sees "
                         f"{len(devs)}")
    return record, peaks(record["kind"])


class CompileEvents:
    """Counts every compile request jax makes (served from the
    persistent cache or not): inside a measured window there must be
    none."""

    def __init__(self):
        import jax

        self.requests = self.misses = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return {"requests": self.requests, "misses": self.misses,
                "hits": self.hits}


class Spans:
    """Host spans of the runner: durations by name on the one clock,
    and — in a traced run — the same spans as ``TraceAnnotation`` so the
    trace's idle gaps can be named after them."""

    def __init__(self, traced):
        self.traced = traced
        self.seconds = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax

        ann = (jax.profiler.TraceAnnotation(name) if self.traced
               else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(
                    time.perf_counter() - t0)


def memory_peak_bytes(devices, planned_bytes=0):
    """The peak on the fullest chip: ``peak_bytes_in_use`` as the
    runtime reports it, or — where that is lower — the bytes the
    compiler planned for the largest program of the window (arguments,
    outputs and temporaries less what is aliased), which the runtime's
    counter was seen to leave out (PERF.md, PR 21)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(max(peaks, default=0), planned_bytes))


def planned_bytes(compiled):
    """Bytes one device holds while ``compiled`` runs, from the
    compiler's own plan."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


@contextlib.contextmanager
def profiler_trace(traced):
    """Trace the enclosed block into a new directory under TMPDIR and
    yield the directory (None when not traced); the caller reduces the
    trace and removes the directory.  The Python tracer is off: the
    spans the runner writes are enough to name the gaps, and it slows a
    host-bound loop."""
    if not traced:
        yield None
        return
    import tempfile

    import jax

    path = tempfile.mkdtemp(prefix="perf_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()
