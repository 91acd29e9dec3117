"""Lint tooling surface: the `paddle lint` CLI (exit codes, structured
output, JSON mode) and scripts/lint_self.sh (the self-lint gate over
demo configs + registry audit)."""

import json
import os
import subprocess
import sys

import paddle_tpu as fluid
from paddle_tpu import layers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PADDLE = os.path.join(REPO, "scripts", "paddle")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args, timeout=300):
    return subprocess.run([sys.executable, PADDLE, "lint", *args],
                          capture_output=True, text=True, env=ENV,
                          timeout=timeout, cwd=REPO)


def _broken_program_json(tmp_path):
    """A program whose op reads a never-written var: PVE01 material."""
    fluid.framework.reset_default_programs()
    block = fluid.default_main_program().global_block()
    block.create_var(name="out", shape=[4], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["never_written"]},
                    outputs={"Out": ["out"]})
    path = tmp_path / "broken.json"
    path.write_text(json.dumps({
        "program": fluid.default_main_program().to_dict(),
        "feed_names": [],
        "fetch_names": ["out"],
    }, default=str))
    return str(path)


def test_lint_broken_program_exits_nonzero(tmp_path):
    out = _run(_broken_program_json(tmp_path))
    assert out.returncode == 1, (out.stdout, out.stderr)
    # structured diagnostic: check id + block + op index on one line
    assert "PVE01" in out.stdout
    assert "block 0 op 0" in out.stdout
    assert "never_written" in out.stdout


def test_lint_json_output_is_parseable(tmp_path):
    out = _run(_broken_program_json(tmp_path), "--json")
    assert out.returncode == 1, (out.stdout, out.stderr)
    diags = json.loads(out.stdout)
    assert any(d["code"] == "PVE01" and d["op_idx"] == 0 for d in diags)


def test_lint_clean_fluid_config_exits_zero(tmp_path):
    conf = tmp_path / "conf.py"
    conf.write_text(
        "import paddle_tpu as fluid\n"
        "x = fluid.layers.data(name='x', shape=[4])\n"
        "y = fluid.layers.fc(input=x, size=3, act='relu')\n")
    out = _run(str(conf))
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "no diagnostics" in out.stdout


def test_lint_inference_export_round_trip(tmp_path):
    """save_inference_model exports lint clean through the .json path
    (program + feed/fetch lists come from __model__.json)."""
    fluid.framework.reset_default_programs()
    x = layers.data(name="x", shape=[6], dtype="float32")
    pred = layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    out = _run(os.path.join(d, "__model__.json"))
    assert out.returncode == 0, (out.stdout, out.stderr)


def test_lint_usage_error():
    out = _run()
    assert out.returncode == 2
    assert "usage" in out.stderr


def test_lint_self_script_green():
    """scripts/lint_self.sh: demo configs + registry audit (+ruff when
    installed) all green — the CI self-lint gate."""
    out = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "lint_self.sh")],
        capture_output=True, text=True, env=ENV, timeout=560, cwd=REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "lint_self OK" in out.stdout


def test_own_environment_variables_ratchet():
    """ROADMAP D5's count: the environment variables of the repo's own
    that the program, the chip smoke and the scripts read (Python's
    ``environ`` / ``getenv`` forms, a shell script's ``${NAME:-default}``),
    less jax's and the system's.  A read may break after its bracket
    (``environ.get(\n"NAME")``): the pattern runs over the file, not a
    line, which is how ``PADDLE_TPU_COORD`` escaped the roadmap's grep."""
    import re

    python = re.compile(r"(?:environ(?:\.get|\.setdefault|\.pop)?|getenv)"
                        r"[\[(]\s*['\"]([A-Z_0-9]+)['\"]")
    shell = re.compile(r"\$\{([A-Z_][A-Z_0-9]*):?[-=]")
    files = [os.path.join(REPO, "chip_smoke.py")]
    for top in ("paddle_tpu", "scripts"):
        for root, _, names in os.walk(os.path.join(REPO, top)):
            files += [os.path.join(root, n) for n in names
                      if top == "scripts" or n.endswith(".py")]
    own = set()
    for path in files:
        with open(path, errors="replace") as f:
            text = f.read()
        own.update(python.findall(text), shell.findall(text))
    own = {v for v in own if not v.startswith("JAX_")
           and v not in ("XLA_FLAGS", "PYTHONPATH", "XDG_CACHE_HOME")}
    LIMIT = 11  # ratchet: only lower this, never raise it
    assert len(own) <= LIMIT, (
        f"{len(own)} environment variables of the repo's own > {LIMIT}: "
        f"{sorted(own)}; a choice is made from what the code can observe, "
        "a value with one caller is a constant (ROADMAP D5)")
