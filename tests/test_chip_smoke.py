"""chip_smoke.py's phase functions at toy size on the CPU, so a later PR
cannot break the script unnoticed.  The script itself has no CPU option:
what a chip run must see (``chip_smoke.EXPECT``) is swapped here for
what the CPU shows — interpreted kernels, no custom-call marker."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TOY = {
    "train": dict(batch=8, image=(3, 32, 32), classes=10, steps=4,
                  small_batch=2),
    # flash dispatches from S >= 1024 in auto mode: S stays, the rest shrinks
    "transformer": dict(B=1, S=1024, D=128, L=1, V=64, steps=2),
    "lstm": dict(B=8, T=5, emb=16, hidden=128, steps=2),
    "softmax": dict(rows=512, cols=128, steps=2),
    # d_v 16: whole tiles of 8 rows, and 4 x (2 x 8 + 16) channels a
    # row of lanes, so both step kernels fit
    "hybrid": dict(slots=4, steps=2, model=dict(
        vocab=96, d_model=32, num_heads=4, head_dim=8, intermediate_size=48,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16, max_len=64,
        num_pages=16, page_size=8, pages_per_seq=4, state_entries=5,
        dtype="float32"),
        # two chunks of the prefill's kernel
        prefill=dict(rows=256, max_len=320, num_pages=48, page_size=8,
                     pages_per_seq=40)),
    "serve": dict(image=(3, 32, 32), classes=10, batches=(1, 3, 4),
                  gen_requests=4, gen_slots=4, gen_tokens=8),
}


@pytest.fixture
def cpu_expectations(monkeypatch):
    from paddle_tpu import amp, pallas as pk

    monkeypatch.setattr(chip_smoke, "EXPECT", {
        "platform": "cpu", "kernel_path": "interpret", "marker": None,
        "memory_stats": False})
    pk.enable("auto", interpret=True)
    yield
    pk.enable("auto", interpret=False)
    amp.enable(False)


def test_refuses_to_run_without_a_tpu():
    """As the driver first runs it: no accelerator -> non-zero exit, no
    result line, no phase run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and "phase" not in proc.stdout
    assert "no tpu" in proc.stderr


def test_device_check_accepts_only_the_expected_platform(cpu_expectations):
    import jax

    dev = chip_smoke.require_device(len(jax.devices()))
    assert dev == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
    with pytest.raises(SystemExit):
        chip_smoke.require_device(len(jax.devices()) + 1)


def test_phase_train_toy(cpu_expectations):
    chip_smoke.phase_train(TOY["train"], seed=0)


def test_phase_kernels_toy(cpu_expectations):
    chip_smoke.phase_kernels(TOY, seed=0)


def test_phase_serve_toy(cpu_expectations):
    chip_smoke.phase_serve(TOY["serve"], seed=0)


def test_flip_explanation_compares_paged_logits(cpu_expectations):
    """The fallback for a greedy flip: teacher-forced logits through the
    paged decode path agree between the kernel and the reference, and a
    stream that differs at a wide logit gap is NOT explained away."""
    import numpy as np

    prompt = [5, 9, 3, 7]
    logits = chip_smoke.forced_decode_logits(prompt, [])
    order = np.argsort(logits)
    best, worst = int(order[-1]), int(order[0])
    with pytest.raises(AssertionError, match="rounding does not explain"):
        chip_smoke._explain_flip(prompt, [best], [worst], tol=1e-6)
    j, diff, gap = chip_smoke._explain_flip(prompt, [best], [worst], tol=10.0)
    assert j == 0 and diff < 1e-4 and gap > 0


def test_phase_multichip_toy(cpu_expectations):
    """The --chips 4 phase on four of the virtual CPU devices."""
    import jax

    toy = dict(resnet=dict(batch=16, image=(3, 64, 64), classes=10, steps=2),
               transformer=dict(B=2, S=2048, D=256, L=1, V=64, steps=2))
    chip_smoke.phase_multichip(toy, seed=0, devices=jax.devices()[:4])
