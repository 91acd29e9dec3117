"""Compiling Executor.

The reference Executor interprets a block op-by-op against device memory
(reference: paddle/framework/executor.cc:36-133).  Per-op dispatch would
leave a TPU idle, so this Executor *compiles*: it traces every op's
lowering rule over a symbolic scope, producing one XLA program for the
whole block, jitted and cached keyed by (program content, feed
signature, fetch set, place).  Repeated ``run`` calls with the same
shapes hit the cache and launch a single device executable.

State (persistable variables — parameters, optimizer moments, BN
statistics) is threaded functionally: the compiled program takes the
state as arguments and returns the written entries.  Buffers the
donation-safety analyzer (paddle_tpu/analysis/optimize.py) proves dead
after their last write are donated, so parameter updates alias in HBM
with no host round-trip; everything else is held undonated.

AOT artifacts (paddle_tpu/aot): on a compile-cache miss the executor
first consults the attached artifact store (per-instance ``aot_store``
or the process-global ``aot.attach``-ed one); a manifest match
deserializes a ``paddle compile``-exported executable instead of
tracing + compiling.  Donation is RESTORED on that path — the
serialized executable carries its input-output aliasing and the
manifest's donation mask is re-proved against the live analyzer before
load.  Any mismatch is a loud JIT fallback counted in
``aot_load_total{result}``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import framework
from paddle_tpu.framework import Program, Variable, TPUPlace, Place
from paddle_tpu.lod import LoDArray
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.events import span
from paddle_tpu.registry import LowerContext, OpRegistry, RngState
from paddle_tpu.sparse import SparseGrad


# ---------------------------------------------------------------------------
# Telemetry (paddle_tpu/observability) — every run() updates these; all
# keyed by program fingerprint so `paddle stats` / GET /metrics can
# attribute cost per compiled program.  Hot-path cost is a handful of
# microseconds (observability.measure_step_overhead), negligible next
# to a step dispatch.
# ---------------------------------------------------------------------------

_M_CACHE_MISS = _metrics.counter(
    "executor_compile_cache_miss_total",
    "Executor.run compile-cache misses, by how the executable was "
    "produced (source=jit: verified, traced, compiled; source=aot: "
    "deserialized from an artifact store)")
_M_CACHE_HIT = _metrics.counter(
    "executor_compile_cache_hit_total",
    "Executor.run compile-cache hits (cached XLA executable reused), "
    "labeled by the executable's original source (jit|aot)")
_M_COMPILE_SEC = _metrics.histogram(
    "executor_compile_seconds",
    "wall time per compile-cache miss: verify + build + jax trace/jit + "
    "first step", buckets=_metrics.COMPILE_TIME_BUCKETS)
_M_FEED_SEC = _metrics.histogram(
    "executor_feed_convert_seconds",
    "host-side feed-dict conversion time per run")
_M_STEP_SEC = _metrics.histogram(
    "executor_step_seconds",
    "step dispatch wall time (cached='miss' rows include trace+compile)")
_M_FETCH_SEC = _metrics.histogram(
    "executor_fetch_seconds",
    "fetch materialization (device->host sync) time per run")
_M_FETCH_BYTES = _metrics.counter(
    "executor_fetch_device_to_host_bytes_total",
    "bytes copied device->host materializing return_numpy fetches")


_M_AOT_EXPORT_SKIPPED = _metrics.counter(
    "executor_aot_export_skipped_total",
    "compile misses inside an aot.capture window whose AOT export "
    "raised and was skipped (the JIT path ran instead)")
_M_DONATION_FAILED = _metrics.counter(
    "executor_donation_analysis_failed_total",
    "compiles whose donation-safety analysis raised, so the step ran "
    "with no donated state")


def _fetch_nbytes(v) -> int:
    """Host bytes a converted fetch value occupies."""
    if isinstance(v, LoDArray):
        return v.data.nbytes + sum(o.nbytes for o in v.lod)
    if isinstance(v, SparseGrad):
        return v.rows.nbytes + v.values.nbytes
    return getattr(v, "nbytes", 0)


# ---------------------------------------------------------------------------
# Scope (reference: paddle/framework/scope.h:38-87)
# ---------------------------------------------------------------------------


class _VarHolder:
    """Minimal compat shim mirroring ``scope.var(name).get_tensor()``."""

    def __init__(self, scope: "Scope", name: str):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self._scope.values.get(self._name)

    def set(self, value, place=None):
        self._scope.values[self._name] = jnp.asarray(value)


class Scope:
    """Name -> device value map with parent-chain lookup."""

    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self.values: Dict[str, Any] = {}
        self.kids: List[Scope] = []

    def new_scope(self) -> "Scope":
        s = Scope(self)
        self.kids.append(s)
        return s

    def var(self, name: str) -> _VarHolder:
        return _VarHolder(self, name)

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.values:
                return _VarHolder(s, name)
            s = s.parent
        return None

    def get(self, name: str, default=None):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.values:
                return s.values[name]
            s = s.parent
        return default

    def set(self, name: str, value):
        self.values[name] = value

    def __contains__(self, name: str) -> bool:
        return self.find_var(name) is not None

    def keys(self):
        return self.values.keys()


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope: Scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


# ---------------------------------------------------------------------------
# Feed conversion
# ---------------------------------------------------------------------------


def _np_dtype(dtype: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16}.get(dtype, np.dtype(dtype))


def _convert_feed(value, var: Optional[Variable]):
    if isinstance(value, LoDArray):
        return value
    if isinstance(value, tuple) and len(value) == 2 and isinstance(value[1], (list, tuple)):
        from paddle_tpu.lod import create_lod_array

        return create_lod_array(np.asarray(value[0]), value[1])
    if isinstance(value, jax.Array):
        # already on device: never round-trip through the host; compare
        # against the canonicalized dtype (int64 -> int32 without x64)
        if var is not None:
            from jax.dtypes import canonicalize_dtype

            target = canonicalize_dtype(_np_dtype(var.dtype))
            if value.dtype != target:
                value = value.astype(target)
        return value
    arr = np.asarray(value)
    if var is not None and arr.dtype != _np_dtype(var.dtype):
        arr = arr.astype(_np_dtype(var.dtype))
    return arr


def _feed_signature(feed_vals: Dict[str, Any]):
    sig = []
    for name in sorted(feed_vals):
        v = feed_vals[name]
        if isinstance(v, LoDArray):
            sig.append(
                (name, "lod", tuple(v.data.shape), str(v.data.dtype),
                 tuple(tuple(o.shape) for o in v.lod))
            )
        else:
            # introspect without materializing (np.asarray on a jax.Array
            # would force a device-to-host copy every step)
            dtype = getattr(v, "dtype", None)
            if dtype is None:
                dtype = np.asarray(v).dtype
            sig.append((name, tuple(np.shape(v)), str(dtype)))
    return tuple(sig)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class _Compiled:
    __slots__ = ("fn", "state_names", "written_names", "fetch_names",
                 "uses_rng", "donated_names", "held_names",
                 "out_state_names", "source")

    def __init__(self, fn, state_names, written_names, fetch_names, uses_rng,
                 donated_names=(), held_names=(), out_state_names=(),
                 source="jit"):
        self.fn = fn
        self.state_names = state_names
        self.written_names = written_names
        self.fetch_names = fetch_names
        self.uses_rng = uses_rng
        self.donated_names = donated_names
        self.held_names = held_names
        self.out_state_names = out_state_names
        self.source = source


def _segment_op_rng(seg_key, op):
    """Deterministic per-op RNG inside a rematerialization segment:
    fold the op's stable __seg_rng_idx__ into the segment key, so the
    forward pass and the (possibly pruned) backward replay derive
    IDENTICAL keys for each random op regardless of which segment ops
    the replay runs."""
    idx = op.attr("__seg_rng_idx__", 0)
    return RngState(jax.random.fold_in(seg_key, idx))


_RANDOM_OPS = frozenset(
    {"uniform_random", "gaussian_random", "dropout", "sampling_id",
     "random_crop", "nce", "segment_rng_key"}
)


class Executor:
    """Whole-block compiling executor.

    ``strategy`` (optional) is a ``paddle_tpu.parallel.Strategy`` that
    supplies a device mesh plus sharding rules for state and feeds; when
    set, compilation goes through ``jax.jit`` with in/out shardings so
    XLA partitions the step program across the mesh (SPMD).
    """

    def __init__(self, place: Optional[Place] = None, strategy=None):
        self.place = place if place is not None else TPUPlace()
        self.strategy = strategy
        self._cache: Dict[Any, _Compiled] = {}
        self._opt_cache: Dict[Any, Any] = {}  # key -> (program, OptReport)
        self._step = 0
        # artifact store consulted at compile misses (paddle_tpu/aot);
        # None -> fall through to the process-global attached store
        self.aot_store = None
        # per-instance boot accounting: how each cache miss was filled
        # (serving uses this to label a replica's boot jit/aot/mixed)
        self.compile_counts = {"jit": 0, "aot": 0}

    # -- public api ---------------------------------------------------------

    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        optimize_program: bool = False,
    ):
        program = program or framework.default_main_program()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        fetch_names = tuple(
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        )

        if optimize_program:
            # rewrite ahead of the compile cache: the OPTIMIZED program's
            # fingerprint keys the cache, so the rewritten executable and
            # the plain one never collide
            program = self._optimized(program, feed, fetch_names)

        with span("executor.run", step=self._step + 1) as run_span:
            return self._run(program, feed, fetch_names, scope,
                             return_numpy, run_span)

    def _run(self, program, feed, fetch_names, scope, return_numpy,
             run_span):
        """One step inside its ``executor.run`` span; the children, in
        order: feed, lookup, compile (misses only), gather_state, step,
        commit_state, fetch."""
        block = program.global_block()
        with span("executor.feed"):
            t_feed = time.perf_counter()
            feed_vals = {
                name: _convert_feed(v, block.find_var(name))
                for name, v in feed.items()
            }
            dt_feed = time.perf_counter() - t_feed

        from paddle_tpu import amp
        from paddle_tpu import pallas as pk
        from paddle_tpu.flags import FLAGS

        with span("executor.lookup"):
            fp = self._program_key(program)
            prog_label = fp[:12]
            key = (
                fp,
                _feed_signature(feed_vals),
                fetch_names,
                self.place,
                id(self.strategy),
                amp.is_enabled(),
                pk.mode(),
                pk.interpret_mode(),
                bool(FLAGS.get("trace_ops")),
            )
            compiled = self._cache.get(key)
        cache_hit = compiled is not None
        tag = "hit" if cache_hit else "miss"
        run_span.set(program=prog_label, cached=tag)
        _M_FEED_SEC.observe(dt_feed, program=prog_label)
        t_compile = time.perf_counter()
        if cache_hit:
            _M_CACHE_HIT.inc(program=prog_label, source=compiled.source)
        else:
            with span("executor.compile", program=prog_label):
                # compile miss: the artifact store (paddle_tpu/aot) gets
                # first refusal — a manifest match deserializes the
                # exported executable (donation intact) instead of
                # trace+compile
                compiled = self._aot_lookup(program, fp, feed_vals,
                                            fetch_names)
                source = "jit" if compiled is None else "aot"
                _M_CACHE_MISS.inc(program=prog_label, source=source)
                self.compile_counts[source] += 1
                if compiled is None:
                    # Pre-compile static checks (paddle_tpu/analysis).
                    # The fetch check always runs — fetching a
                    # never-written variable must name the variable up
                    # front, not die as a KeyError mid-trace.  With the
                    # check_program flag on, the full error tier runs
                    # (def-before-use, dtype clash, bad sub-blocks, ...)
                    # before any JAX tracing.  Cache hits skip both:
                    # already vetted.
                    self._verify(program, feed_vals, fetch_names)
                    compiled = self._compile(program, feed_vals,
                                             fetch_names, scope)
            self._cache[key] = compiled

        with span("executor.gather_state"):
            state = {}
            missing = []
            for n in compiled.state_names:
                v = scope.get(n)
                if v is None:
                    missing.append(n)
                state[n] = v
        if missing:
            raise RuntimeError(
                f"persistable variables not initialized in scope: {missing}; "
                "run the startup program first"
            )

        if not cache_hit and compiled.source == "jit":
            # export capture (aot.capture): lower this step AOT with the
            # concrete args, serialize it into the active writer, and run
            # the captured executable itself so the export is validated
            # by execution
            exported = self._aot_export(program, fp, compiled, state,
                                        feed_vals)
            if exported is not None:
                compiled = exported
                self._cache[key] = compiled

        self._step += 1
        args = [state, feed_vals]
        if compiled.uses_rng:
            args.append(np.int64(self._seed_for_step(program)))
        with span("executor.step"):
            t_step = time.perf_counter()
            fetches, new_state = compiled.fn(*args)
            dt_step = time.perf_counter() - t_step
        _M_STEP_SEC.observe(dt_step, program=prog_label, cached=tag)
        if not cache_hit:
            # trace + jit + the first (compiling) dispatch: jax defers
            # tracing/XLA work to the first call, so the honest
            # per-compile wall time spans through that call
            _M_COMPILE_SEC.observe(time.perf_counter() - t_compile,
                                   program=prog_label)

        with span("executor.commit_state"):
            for n, v in new_state.items():
                scope.set(n, v)

        if not (return_numpy and fetches):
            return list(fetches)
        with span("executor.fetch"):
            t_fetch = time.perf_counter()
            out = []
            nbytes = 0
            for v in fetches:
                if isinstance(v, LoDArray):
                    v = LoDArray(np.asarray(v.data),
                                 tuple(np.asarray(o) for o in v.lod))
                elif isinstance(v, SparseGrad):
                    v = SparseGrad(np.asarray(v.rows), np.asarray(v.values),
                                   v.height)
                else:
                    v = np.asarray(v)
                nbytes += _fetch_nbytes(v)
                out.append(v)
            _M_FETCH_SEC.observe(time.perf_counter() - t_fetch,
                                 program=prog_label)
        if nbytes:
            _M_FETCH_BYTES.inc(nbytes, program=prog_label)
        return out

    # -- internals ----------------------------------------------------------

    def _optimized(self, program: Program, feed: Dict[str, Any],
                   fetch_names: Sequence[str]) -> Program:
        """Memoized rewrite-pipeline front end for run(optimize_program=
        True).  The pipeline is parity-gated internally (verify-or-revert
        per pass); a program the verifier rejects comes back unchanged."""
        from paddle_tpu.analysis import optimize as _opt

        key = (self._program_key(program), tuple(sorted(feed)), fetch_names)
        hit = self._opt_cache.get(key)
        if hit is None:
            hit = _opt.optimize_program(
                program, feed_names=set(feed), fetch_names=fetch_names)
            self._opt_cache[key] = hit
        return hit[0]

    def optimize_report(self, program: Program, feed: Dict[str, Any],
                        fetch_names: Sequence[str]):
        """The OptReport from a prior run(optimize_program=True) with the
        same (program, feed names, fetches); None before any such run."""
        key = (self._program_key(program), tuple(sorted(feed)),
               tuple(fetch_names))
        hit = self._opt_cache.get(key)
        return hit[1] if hit is not None else None

    @staticmethod
    def _verify(program: Program, feed_vals: Dict[str, Any],
                fetch_names: Sequence[str]):
        from paddle_tpu import analysis
        from paddle_tpu.flags import FLAGS

        if FLAGS.get("check_program"):
            analysis.check_or_raise(
                program, feed_names=set(feed_vals), fetch_names=fetch_names,
                header="program rejected before compile "
                       "(flag check_program=1)")
            return
        # flag off: still catch the cheapest, most opaque failure mode —
        # a fetch target nothing writes — with a clear error
        diags = analysis.verify_program(
            program, feed_names=set(feed_vals), fetch_names=fetch_names,
            only=("fetch-reachability",))
        if diags:
            raise RuntimeError(
                "; ".join(d.message for d in diags)
                + " — run with flags check_program=1 for full program "
                  "verification")

    def _seed_for_step(self, program: Program) -> int:
        base = program.seed if program.seed is not None else 0
        return np.int64(base * 1000003 + self._step)

    @staticmethod
    def _program_key(program: Program):
        # Cheap structural key: recompute the content hash only when the
        # op/var counts OR the attr-mutation version change (Operator
        # attrs version-bump the program on any in-place write, so a
        # hand-flipped ``is_test`` recompiles instead of silently
        # reusing the stale executable).
        counts = (tuple((len(b.ops), len(b.vars)) for b in program.blocks),
                  getattr(program, "_version", 0))
        cached = getattr(program, "_fp_cache", None)
        if cached is not None and cached[0] == counts:
            return cached[1]
        fp = program.fingerprint()
        program._fp_cache = (counts, fp)
        return fp

    # -- AOT artifacts (paddle_tpu/aot) -------------------------------------

    def _aot_active_store(self):
        """The artifact store this executor should consult: its own
        ``aot_store`` first, else the process-global attached one.  The
        sys.modules probe keeps the hot path import-free: if nothing
        ever imported paddle_tpu.aot, no store can be attached."""
        if self.aot_store is not None:
            return self.aot_store
        import sys as _sys

        mod = _sys.modules.get("paddle_tpu.aot")
        return mod.active_store() if mod is not None else None

    def _current_donated(self, program, feed_vals, fetch_names,
                         state_names) -> tuple:
        """The donation mask _compile would prove right now — the AOT
        load side re-derives it and refuses an entry on drift (the
        serialized executable's aliasing is baked in)."""
        if not state_names:
            return ()
        from paddle_tpu.analysis import optimize as _opt

        try:
            donation = _opt.donation_mask(
                program, set(feed_vals), fetch_names)
        except Exception:
            return ()
        return tuple(n for n in state_names
                     if n in donation and donation[n].eligible)

    def _aot_lookup(self, program, fp, feed_vals, fetch_names):
        """Consult the artifact store for this cache miss; returns a
        ready _Compiled (source="aot") or None for the JIT path."""
        if self.strategy is not None:
            return None  # sharded steps are not exported
        store = self._aot_active_store()
        if store is None:
            return None
        from paddle_tpu.aot import artifact as _art

        sig = _art.sig_json(_feed_signature(feed_vals))

        def _validate(meta):
            expect = tuple(meta.get("donated_names", ()))
            have = self._current_donated(program, feed_vals, fetch_names,
                                         tuple(meta["state_names"]))
            if expect != have:
                return (f"donation_drift: manifest donates {expect}, "
                        f"live analysis proves {have}")
            return None

        hit = store.lookup(fp, sig, fetch_names, validate=_validate)
        if hit is None:
            return None
        meta, loaded = hit
        return self._wrap_aot(loaded, meta)

    def _wrap_aot(self, executable, meta: dict) -> _Compiled:
        """Adapt a (deserialized or freshly lowered) jax.stages.Compiled
        to the _Compiled calling convention fn(state, feeds[, seed]).

        Donation hygiene: unlike jax.jit, a raw Compiled call donates
        whatever buffer it is handed — including one zero-copied from a
        host numpy array (jnp.asarray aliases aligned host memory on
        CPU), whose in-place overwrite would corrupt the caller's array.
        So a donated input is defensively copied UNLESS it is this
        executable's own previous output (an XLA-owned buffer): the
        first step per state entry pays one copy, every steady-state
        step donates for free."""
        donated = tuple(meta["donated_names"])
        held = tuple(meta["held_names"])
        last_out: Dict[str, Any] = {}

        def fn(state, feeds, *rest):
            dvals = {}
            for n in donated:
                v = state[n]
                if last_out.get(n) is not v:
                    v = jnp.array(v, copy=True)
                dvals[n] = v
            fetches, new_state = executable(
                dvals, {n: state[n] for n in held}, feeds, *rest)
            for n in donated:
                if n in new_state:
                    last_out[n] = new_state[n]
            return fetches, new_state

        return _Compiled(fn, tuple(meta["state_names"]),
                         tuple(meta["written_names"]),
                         tuple(meta["fetch_names"]),
                         bool(meta["uses_rng"]),
                         donated_names=donated, held_names=held,
                         out_state_names=tuple(meta["out_state_names"]),
                         source="aot")

    def _aot_export(self, program, fp, compiled: _Compiled, state,
                    feed_vals) -> Optional[_Compiled]:
        """When an aot.capture window is active, lower this fresh JIT
        compile ahead-of-time, serialize it into the writer, and return
        the captured executable wrapped for execution.  Any failure
        (e.g. an unserializable program) leaves the JIT path untouched."""
        if self.strategy is not None:
            return None
        import sys as _sys

        mod = _sys.modules.get("paddle_tpu.aot")
        writer = mod.active_exporter() if mod is not None else None
        if writer is None:
            return None
        rest = (np.int64(self._seed_for_step(program)),) \
            if compiled.uses_rng else ()
        try:
            lowered = compiled.fn.lower(state, feed_vals, *rest)
            with mod.artifact.compiled_afresh():
                executable = lowered.compile()
            meta = writer.add(
                program_fp=fp,
                feed_sig=_feed_signature(feed_vals),
                fetch_names=compiled.fetch_names,
                executable=executable,
                state_names=compiled.state_names,
                donated_names=compiled.donated_names,
                held_names=compiled.held_names,
                out_state_names=compiled.out_state_names,
                written_names=compiled.written_names,
                uses_rng=compiled.uses_rng)
        except Exception as exc:
            import sys

            _M_AOT_EXPORT_SKIPPED.inc()
            print(f"[paddle_tpu.aot] export skipped for program "
                  f"{fp[:12]}: {type(exc).__name__}: {exc}",
                  file=sys.stderr)
            return None
        return self._wrap_aot(executable, meta)

    def build_callable(self, program: Program, feed_vals: Dict[str, Any],
                       fetch_names: Sequence[str], scope: Optional[Scope] = None):
        """Return ``(fn, state)``: a pure jittable ``fn(state, feeds[, seed])
        -> (fetches, new_state)`` plus the current state dict from scope.
        This is the functional view of one executor step — what the jit
        cache wraps, exposed for embedding into outer JAX code."""
        scope = scope or global_scope()
        feed_vals = {
            name: _convert_feed(v, program.global_block().find_var(name))
            for name, v in feed_vals.items()
        }
        compiled = self._compile(program, feed_vals, fetch_names, scope, jit=False)
        state = {n: scope.get(n) for n in compiled.state_names}
        missing = [n for n, v in state.items() if v is None]
        if missing:
            raise RuntimeError(f"uninitialized persistables: {missing}")
        return compiled.fn, state, feed_vals, compiled.uses_rng

    def _compile(
        self,
        program: Program,
        feed_vals: Dict[str, Any],
        fetch_names: Sequence[str],
        scope: Scope,
        jit: bool = True,
    ) -> _Compiled:
        block = program.global_block()

        # Classify variables: anything persistable that an op reads and
        # that is not fed comes from the state dict; persistable outputs
        # go back into it (functional in-place update).
        read_state: List[str] = []
        written_state: List[str] = []
        produced: set = set(feed_vals)
        uses_rng = False
        for op in block.ops:
            if op.type in ("feed", "fetch"):
                continue
            if op.type in _RANDOM_OPS and not op.attr("is_test", False):
                uses_rng = True
            for n in op.input_arg_names:
                if not n:
                    continue  # pruned grad slot
                var = block.find_var(n)
                if n in produced or n in read_state:
                    continue
                if var is not None and var.persistable:
                    read_state.append(n)
                elif n not in produced:
                    # non-persistable, never produced: must be fed
                    if n not in feed_vals:
                        raise RuntimeError(
                            f"op {op.type} reads {n!r} which is neither fed, "
                            f"produced by an earlier op, nor persistable"
                        )
            for n in op.output_arg_names:
                if not n:
                    continue
                produced.add(n)
                var = block.find_var(n)
                if var is not None and var.persistable and n not in written_state:
                    written_state.append(n)
        for n in fetch_names:
            if n not in produced and n not in read_state:
                var = block.find_var(n)
                if var is not None and var.persistable:
                    read_state.append(n)
                elif n not in feed_vals:
                    raise RuntimeError(f"fetch target {n!r} is never produced")

        # inputs: persistables that are read before being written.
        # outputs: the jit path returns only the persistables actually
        # WRITTEN (the scope already holds every read-only buffer;
        # returning those would force XLA output copies now that
        # donation is per-entry).  The un-jitted path (build_callable)
        # keeps the historical read+written contract: callers scan over
        # fn with the state dict as the loop carry, so input and output
        # state must share a pytree structure.
        state_names = tuple(read_state)
        if jit:
            out_state_names = tuple(dict.fromkeys(written_state))
        else:
            out_state_names = tuple(dict.fromkeys(read_state + written_state))
        written_names = tuple(written_state)

        # Donation-safety mask (analysis/optimize.py): donate a state
        # buffer only when liveness PROVES no op can observe the old
        # value — overwritten at top level, never read after its last
        # write, never aliased into a control-flow sub-block.  This
        # replaces the old all-or-nothing donate_argnums=(0,) on the
        # whole state dict.
        donated_names: tuple = ()
        donation = {}
        if jit and state_names:
            from paddle_tpu.analysis import optimize as _opt

            try:
                donation = _opt.donation_mask(
                    program, set(feed_vals), fetch_names)
            except Exception:
                donation = {}  # analysis must never block execution
                _M_DONATION_FAILED.inc()
            donated_names = tuple(
                n for n in state_names
                if n in donation and donation[n].eligible)
        held_names = tuple(n for n in state_names if n not in donated_names)
        if jit and donation:
            from paddle_tpu.analysis import optimize as _opt

            _opt.set_donation_gauge(self._program_key(program)[:12], donation)
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]

        strategy = self.strategy

        # Opt-in per-op tracing (flags trace_ops=1): jax.named_scope
        # threads "<op_type>_<idx>" into the HLO op metadata so xprof/
        # tensorboard traces show op names instead of anonymous fused
        # regions, and TraceAnnotation marks the same span on the host
        # timeline when the block runs un-jitted (build_callable).  The
        # flag is part of the compile-cache key — flipping it retraces.
        from paddle_tpu.flags import FLAGS

        trace_ops = bool(FLAGS.get("trace_ops"))
        op_index = {id(op): i for i, op in enumerate(ops)}

        def _lower_op(op, vals, op_rng):
            info = OpRegistry.get(op.type)
            ctx = LowerContext(op, vals, rng=op_rng, executor_ctx=program)
            if trace_ops:
                i = op_index[id(op)]
                with jax.named_scope(f"{op.type}_{i}"), \
                        jax.profiler.TraceAnnotation(f"{op.type}:{i}"):
                    info.lower(ctx)
            else:
                info.lower(ctx)

        # Rematerialization segments (fluid.recompute_scope): group
        # consecutive forward ops sharing a __recompute_seg__ id.  A
        # segment's intermediates stay LOCAL — only values consumed by
        # later ops / fetches / state leave it — and its matching
        # recompute_segment_grad op (backward.py) re-derives the
        # forward from the segment inputs inside its own vjp, so the
        # intermediates are never live across the fwd->bwd span: the
        # activation-memory/FLOPs trade jax.checkpoint makes, expressed
        # at the program level where this framework's AD lives.
        op_groups: List[Any] = []
        for op in ops:
            seg = op.attr("__recompute_seg__", None)
            if op_groups and op_groups[-1][0] == seg:
                op_groups[-1][1].append(op)
            else:
                op_groups.append((seg, [op]))

        # per segment: names its later consumers need (externally
        # visible); everything else is segment-local.  One reverse
        # suffix pass keeps this O(N) for many segments.
        seg_exports: Dict[int, tuple] = {}
        suffix_reads = set(fetch_names) | set(out_state_names)
        for seg, seg_ops in reversed(op_groups):
            if seg is not None:
                written = set()
                for op in seg_ops:
                    for ns in op.outputs.values():
                        written.update(n for n in ns if n)
                seg_exports[id(seg_ops[0])] = tuple(
                    sorted(written & suffix_reads))
            for op in seg_ops:
                for ns in op.inputs.values():
                    suffix_reads.update(n for n in ns if n)

        def run_block(state, feeds, seed=None):
            from paddle_tpu.parallel.strategy import strategy_scope

            values: Dict[str, Any] = {}
            values.update(state)
            values.update(feeds)
            rng = RngState(jax.random.key(seed)) if seed is not None else None
            with strategy_scope(strategy):
                for seg, seg_ops in op_groups:
                    if seg is None:
                        for op in seg_ops:
                            _lower_op(op, values, rng)
                        continue
                    # the segment's randomness comes from its key op's
                    # output (shared with the backward recompute)
                    seg_key = values.get(f"__segkey_{seg}__")
                    local = dict(values)
                    for op in seg_ops:
                        # per-op key folded from the segment key and the
                        # op's stable index (no key value — e.g. startup
                        # init ops created inside the scope — falls back
                        # to the plain outer rng)
                        op_rng = (_segment_op_rng(seg_key, op)
                                  if seg_key is not None else rng)
                        _lower_op(op, local, op_rng)
                    for n in seg_exports[id(seg_ops[0])]:
                        values[n] = local[n]
            fetches = [values[n] for n in fetch_names]
            new_state = {n: values[n] for n in out_state_names}
            return fetches, new_state

        if not jit:
            return _Compiled(run_block, state_names, written_names, fetch_names,
                             uses_rng, held_names=state_names,
                             out_state_names=out_state_names)

        # The jitted step takes (donated_state, held_state, feeds[, seed])
        # so donate_argnums=(0,) donates exactly the buffers the mask
        # proved safe; the public _Compiled.fn keeps the historical
        # fn(state, feeds[, seed]) calling convention and splits the dict.
        def paddle_step(donated, held, feeds, seed=None):
            merged = dict(held)
            merged.update(donated)
            with jax.named_scope("paddle_step"):
                return run_block(merged, feeds, seed)

        jit_kwargs: Dict[str, Any] = (
            {"donate_argnums": (0,)} if donated_names else {})
        if self.strategy is not None:
            sh = self.strategy.jit_shardings(
                block, state_names, sorted(feed_vals), uses_rng=uses_rng,
                out_state_names=out_state_names,
            )
            state_sh = sh["in_shardings"][0]
            jit_kwargs["in_shardings"] = (
                {n: state_sh[n] for n in donated_names},
                {n: state_sh[n] for n in held_names},
            ) + tuple(sh["in_shardings"][1:])
            jit_kwargs["out_shardings"] = sh["out_shardings"]
        # the function's name is the module's in a device trace:
        # jit_paddle_step, whatever the program
        jfn = jax.jit(paddle_step, **jit_kwargs)
        # A place that names a device (CPUPlace) commits every argument
        # to it, so the step runs there whatever the default backend is;
        # TPUPlace leaves placement to jax's default device.
        device = self.place.device() if self.strategy is None else None

        def _args(state, feeds, rest):
            args = ({n: state[n] for n in donated_names},
                    {n: state[n] for n in held_names}, feeds) + rest
            return args if device is None else jax.device_put(args, device)

        def _placed():
            return (contextlib.nullcontext() if device is None
                    else jax.default_device(device))

        def fn(state, feeds, *rest):
            with _placed():
                return jfn(*_args(state, feeds, rest))

        # preserve the jitted object's introspection surface through the
        # wrapper (tests/benchmarks call compiled.fn.lower(state, feeds))
        def _lower(state, feeds, *rest):
            with _placed():
                return jfn.lower(*_args(state, feeds, rest))

        fn.lower = _lower
        return _Compiled(fn, state_names, written_names, fetch_names, uses_rng,
                         donated_names=donated_names, held_names=held_names,
                         out_state_names=out_state_names)
