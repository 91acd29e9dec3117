"""Differential tests for the whole-program optimizer
(paddle_tpu/analysis/optimize.py): every rewrite the pipeline makes
must be invisible at the fetch surface — bit-identical outputs, a
verifier-clean program — and the donation-safety analyzer must reject
exactly the aliasing shapes that would corrupt state."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, layers
from paddle_tpu import executor as executor_mod
from paddle_tpu.analysis import dataflow, optimize


@pytest.fixture(autouse=True)
def _fresh():
    fluid.framework.reset_default_programs()
    yield


B, D = 4, 8

# deterministic subset of the fuzz alphabet: no dropout (its RNG draw
# is kept by every pass, but two independent Executors seed their key
# streams independently, which is run-to-run noise, not optimizer skew)
_UNARY = [
    ("relu", lambda x: layers.relu(x)),
    ("tanh", lambda x: layers.tanh(x)),
    ("sigmoid", lambda x: layers.sigmoid(x)),
    ("scale", lambda x: layers.scale(x, scale=0.5, bias=0.1)),
    ("fc_relu", lambda x: layers.fc(input=x, size=D, act="relu")),
    ("fc_lin", lambda x: layers.fc(input=x, size=D)),
    ("softmax", lambda x: layers.softmax(x)),
    ("abs", lambda x: layers.abs(x)),
    ("square", lambda x: layers.square(x)),
]

_BINARY = [
    ("add", lambda a, b: layers.elementwise_add(x=a, y=b)),
    ("mul", lambda a, b: layers.elementwise_mul(x=a, y=b)),
    ("sub", lambda a, b: layers.elementwise_sub(x=a, y=b)),
]


def _build_chain(rng):
    x = layers.data(name="x", shape=[D], dtype="float32")
    names, frontier = [], [x]
    for _ in range(rng.randint(3, 7)):
        if len(frontier) >= 2 and rng.rand() < 0.3:
            i, j = rng.choice(len(frontier), 2, replace=False)
            nm, op = _BINARY[rng.randint(len(_BINARY))]
            out = op(frontier[i], frontier[j])
        else:
            src = frontier[rng.randint(len(frontier))]
            nm, op = _UNARY[rng.randint(len(_UNARY))]
            out = op(src)
        names.append(nm)
        frontier.append(out)
    return names, frontier[-1]


def _startup_state(program):
    """Run the startup program once and capture every persistable the
    main program declares — the shared initial state both sides of the
    differential harness start from."""
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    exe.run(fluid.default_startup_program(), scope=scope)
    state = {}
    for name, var in program.global_block().vars.items():
        if var.persistable and name in scope:
            state[name] = np.asarray(scope.get(name))
    return state


# ---------------------------------------------------------------------------
# Differential fuzzer: optimized == original, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(10))
def test_fuzzed_chain_optimizes_with_bit_parity(seed):
    """Random layer chains (training on odd seeds) through the full
    pipeline: fetches must be bit-identical and the optimized program
    must still verify clean at the error tier."""
    rng = np.random.RandomState(7000 + seed)
    names, out = _build_chain(rng)
    feed = {"x": rng.randn(B, D).astype("float32") * 0.5}
    fetches = [out.name]
    if seed % 2:
        label = layers.data(name="y", shape=[D], dtype="float32")
        loss = layers.mean(
            layers.square_error_cost(input=out, label=label))
        fluid.optimizer.SGD(learning_rate=1e-3).minimize(loss)
        feed["y"] = rng.randn(B, D).astype("float32") * 0.5
        fetches = [loss.name]

    program = fluid.default_main_program()
    state = _startup_state(program)
    try:
        report = optimize.check_parity(program, feed, fetches, state=state)
    except AssertionError:
        raise AssertionError(f"chain {names} (seed {seed}) broke parity")
    assert report.optimized

    optimized, _ = optimize.optimize_program(
        program, feed_names=set(feed), fetch_names=fetches)
    diags = analysis.verify_program(optimized, feed_names=set(feed),
                                    fetch_names=fetches, level="error")
    assert not diags, (
        f"chain {names} (seed {seed}) optimized into an invalid "
        f"program:\n" + analysis.format_report(diags))


# ---------------------------------------------------------------------------
# Targeted pass semantics
# ---------------------------------------------------------------------------


def test_cse_merges_top_level_but_never_across_blocks():
    """Two identical top-level scales merge; the identical scale inside
    a While sub-block must NOT be merged with them — it runs under the
    loop's control flow, a different number of times."""
    x = layers.data(name="x", shape=[4], dtype="float32",
                    append_batch_size=False)
    a = layers.scale(x, scale=2.0)
    b = layers.scale(x, scale=2.0)  # duplicate of a
    out_top = layers.elementwise_add(x=a, y=b)

    i = layers.fill_constant(shape=(1,), dtype="float32", value=0.0)
    n = layers.fill_constant(shape=(1,), dtype="float32", value=3.0)
    acc = layers.fill_constant(shape=(4,), dtype="float32", value=0.0)
    cond = layers.less_than(i, n)
    w = layers.While(cond)
    with w.block():
        s = layers.scale(x, scale=2.0)  # same key, inside the loop
        layers.assign(layers.elementwise_add(x=acc, y=s), output=acc)
        layers.increment(i, value=1.0, in_place=True)
        layers.assign(layers.less_than(i, n), output=cond)

    program = fluid.default_main_program()
    feed = {"x": np.arange(4, dtype="float32")}
    fetches = [out_top.name, acc.name]

    optimized, report = optimize.optimize_program(
        program, feed_names={"x"}, fetch_names=fetches)
    assert report.cse_hits >= 1, report.format()

    sub_scales = []
    for op in optimized.global_block().ops:
        for _, sub in dataflow.op_sub_blocks(op):
            for _b, _i, sub_op in dataflow.walk_ops(sub):
                if sub_op.type == "scale":
                    sub_scales.append(sub_op)
    assert sub_scales, "sub-block scale was merged across blocks"

    optimize.check_parity(program, feed, fetches)


def test_constant_fold_preserves_dtype():
    """int32 + int32 folds to an int32 fill; the cast to float16 folds
    to a float16 fill — the fold must carry the computed dtype, not
    default to float32."""
    c1 = layers.fill_constant(shape=(2, 2), dtype="int32", value=3)
    c2 = layers.fill_constant(shape=(2, 2), dtype="int32", value=4)
    s = layers.elementwise_add(x=c1, y=c2)
    f = layers.cast(s, "float16")

    program = fluid.default_main_program()
    optimized, report = optimize.optimize_program(
        program, feed_names=set(), fetch_names=[s.name, f.name])
    assert report.folds >= 2, report.format()

    by_out = {}
    for op in optimized.global_block().ops:
        for name in op.output_arg_names:
            by_out[name] = op
    assert by_out[s.name].type == "fill"
    assert by_out[s.name].attr("dtype") == "int32"
    assert np.asarray(by_out[s.name].attr("data")).dtype == np.int32
    assert (np.asarray(by_out[s.name].attr("data")) == 7).all()
    assert by_out[f.name].type == "fill"
    assert by_out[f.name].attr("dtype") == "float16"

    optimize.check_parity(program, {}, [s.name, f.name])


def test_dce_keeps_unfetched_random_ops():
    """A dropout nothing fetches must survive DCE: random ops split the
    step's RNG key in program order, so removing one would shift every
    later random op's key stream."""
    x = layers.data(name="x", shape=[D], dtype="float32")
    layers.dropout(layers.scale(x, scale=1.5), dropout_prob=0.3)
    y = layers.scale(x, scale=2.0)

    program = fluid.default_main_program()
    optimized, _ = optimize.optimize_program(
        program, feed_names={"x"}, fetch_names=[y.name])
    assert any(op.type == "dropout"
               for op in optimized.global_block().ops)


# ---------------------------------------------------------------------------
# Donation-safety analyzer
# ---------------------------------------------------------------------------


def test_donation_rejects_read_after_last_write():
    """The PR-15 corruption shape, hand-built: state W is overwritten
    and then read again by a later top-level op.  Donating W would let
    XLA clobber the buffer that later read still needs — the analyzer
    must hold it.  The control (no read after the write) is eligible."""
    x = layers.data(name="x", shape=[4], dtype="float32",
                    append_batch_size=False)
    w = layers.create_global_var(shape=(4,), value=1.0, dtype="float32",
                                 persistable=True, name="w_state")
    v = layers.create_global_var(shape=(4,), value=2.0, dtype="float32",
                                 persistable=True, name="v_state")

    t = layers.elementwise_add(x=w, y=x)
    layers.assign(t, output=w)              # last write of w
    z = layers.elementwise_add(x=w, y=x)    # read AFTER the last write

    layers.assign(layers.elementwise_mul(x=v, y=x), output=v)  # clean

    program = fluid.default_main_program()
    mask = optimize.donation_mask(program, {"x"}, [z.name])

    assert not mask["w_state"].eligible
    assert mask["w_state"].reason.startswith("read after last write")
    assert mask["v_state"].eligible, mask["v_state"].reason


def test_donation_rejects_sub_block_alias_and_read_only():
    """State read inside a While sub-block is invisible to top-level
    last-write ordering — never donatable.  Read-only state has no
    aliasing write at all — donating it only destroys the scope copy."""
    x = layers.data(name="x", shape=[4], dtype="float32",
                    append_batch_size=False)
    w = layers.create_global_var(shape=(4,), value=1.0, dtype="float32",
                                 persistable=True, name="w_loop")
    r = layers.create_global_var(shape=(4,), value=3.0, dtype="float32",
                                 persistable=True, name="r_only")

    layers.assign(layers.elementwise_add(x=w, y=x), output=w)
    ro = layers.elementwise_mul(x=r, y=x)   # r never written

    i = layers.fill_constant(shape=(1,), dtype="float32", value=0.0)
    n = layers.fill_constant(shape=(1,), dtype="float32", value=2.0)
    acc = layers.fill_constant(shape=(4,), dtype="float32", value=0.0)
    cond = layers.less_than(i, n)
    loop = layers.While(cond)
    with loop.block():
        layers.assign(layers.elementwise_add(x=acc, y=w), output=acc)
        layers.increment(i, value=1.0, in_place=True)
        layers.assign(layers.less_than(i, n), output=cond)

    program = fluid.default_main_program()
    mask = optimize.donation_mask(program, {"x"}, [acc.name, ro.name])

    assert not mask["w_loop"].eligible
    assert mask["w_loop"].reason == "aliased into a sub-block"
    assert not mask["r_only"].eligible
    assert "read-only" in mask["r_only"].reason


# ---------------------------------------------------------------------------
# Integration: the three wiring points
# ---------------------------------------------------------------------------


def test_executor_optimize_flag_matches_plain_run():
    """Executor.run(optimize_program=True) must train bit-identically
    to the unoptimized run from the same initial state."""
    x = layers.data(name="x", shape=[D], dtype="float32")
    label = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(input=x, size=D, act="relu")
    pred = layers.fc(input=h, size=1)
    loss = layers.mean(layers.square_error_cost(input=pred, label=label))
    fluid.optimizer.Momentum(learning_rate=1e-2, momentum=0.9).minimize(loss)

    program = fluid.default_main_program()
    state = _startup_state(program)
    rng = np.random.RandomState(11)
    feed = {"x": rng.randn(B, D).astype("float32"),
            "y": rng.randn(B, 1).astype("float32")}

    def train(optimize_flag):
        scope = executor_mod.Scope()
        for name, value in state.items():
            scope.set(name, np.array(value, copy=True))
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        for _ in range(4):
            (l,) = exe.run(program, feed=feed, fetch_list=[loss],
                           scope=scope, optimize_program=optimize_flag)
            losses.append(np.asarray(l))
        return losses

    plain, optimized = train(False), train(True)
    for a, b in zip(plain, optimized):
        np.testing.assert_array_equal(a, b)


def test_executor_exposes_optimize_report():
    x = layers.data(name="x", shape=[D], dtype="float32")
    y = layers.scale(layers.scale(x, scale=2.0), scale=3.0)
    layers.scale(x, scale=9.0)  # dead: no fetch depends on it

    program = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((B, D), np.float32)}
    exe.run(program, feed=feed, fetch_list=[y], optimize_program=True)
    report = exe.optimize_report(program, feed, (y.name,))
    assert report is not None and report.optimized
    assert report.dce_ops_removed >= 1


def test_model_bundle_serves_optimized_program(tmp_path):
    """ModelBundle(optimize=True) must produce the same predictions as
    the raw export, and carry the optimizer report."""
    from paddle_tpu.serving.replica import ModelBundle, Replica

    x = layers.data(name="x", shape=[4], dtype="float32")
    pred = layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)

    feeds = {"x": np.random.RandomState(3).randn(5, 4).astype("float32")}
    raw = Replica(ModelBundle(d, optimize=False), 0,
                  place=fluid.CPUPlace()).run(feeds)
    bundle = ModelBundle(d, optimize=True)
    opt = Replica(bundle, 0, place=fluid.CPUPlace()).run(feeds)

    assert bundle.opt_report is not None and bundle.opt_report.optimized
    for a, b in zip(raw, opt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mnist_demo_config_optimizes_with_bit_parity():
    """The bundled v1 MNIST demo through the differential harness:
    the optimizer must be invisible on a real training step."""
    from paddle_tpu.trainer.config_parser import parse_config
    from paddle_tpu.v2.topology import Topology

    conf = parse_config("demos/mnist_v1/trainer_config.py", "")
    topo = Topology(conf.cost, extra_layers=conf.evaluators)
    program = topo.main_program
    fetches = [v.name for v in topo.output_vars]

    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    exe.run(topo.startup_program, scope=scope)
    state = {n: np.asarray(scope.get(n))
             for n, v in program.global_block().vars.items()
             if v.persistable and n in scope}

    rng = np.random.RandomState(0)
    feed = {"pixel": rng.rand(8, 784).astype("float32"),
            "label": rng.randint(0, 10, size=(8, 1)).astype("int64")}
    report = optimize.check_parity(program, feed, fetches, state=state)
    assert report.optimized


def test_serving_mlp_demo_config_optimizes_with_bit_parity():
    """The bundled serving MLP demo (the lint --optimize smoke target)
    through the differential harness."""
    from paddle_tpu import framework

    main, startup = framework.Program(), framework.Program()
    target = "demos/serving_mlp/infer_config.py"
    with framework.program_guard(main, startup):
        glb = {"__file__": target, "__name__": "__paddle_lint__"}
        with open(target) as f:
            exec(compile(f.read(), target, "exec"), glb)

    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    exe.run(startup, scope=scope)
    state = {n: np.asarray(scope.get(n))
             for n, v in main.global_block().vars.items()
             if v.persistable and n in scope}

    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(6, 32).astype("float32")}
    report = optimize.check_parity(main, feed, ["prediction"], state=state)
    assert report.optimized


def test_backward_slice_subsumes_prune():
    """Program.prune delegates to the optimizer's backward slice: the
    sliced program drops the optimizer update but keeps everything the
    target needs, and still verifies clean."""
    x = layers.data(name="x", shape=[D], dtype="float32")
    label = layers.data(name="y", shape=[D], dtype="float32")
    out = layers.fc(input=x, size=D, act="relu")
    loss = layers.mean(layers.square_error_cost(input=out, label=label))
    fluid.optimizer.SGD(learning_rate=1e-3).minimize(loss)

    program = fluid.default_main_program()
    sliced = program.prune([out])
    types = [op.type for op in sliced.global_block().ops]
    assert "sgd" not in types
    assert any(t in ("mul", "matmul") for t in types)
    diags = analysis.verify_program(sliced, feed_names={"x"},
                                    fetch_names=[out.name], level="error")
    assert not diags, analysis.format_report(diags)
