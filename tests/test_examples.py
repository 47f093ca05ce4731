"""Run the shipped examples with PLANTED CONVERGENCE assertions
(smoke-only example tests keep nothing honest — the reference's examples
are its de-facto tutorial surface). The synthetic
tasks carry a class-dependent pattern, so a working training loop must
LEARN it: losses fall across epochs (fresh batches each epoch — this is
generalization on the planted pattern, not memorization) and
train-subset accuracy beats chance."""

import os
import re
import subprocess
import sys

_EX = os.path.join(os.path.dirname(__file__), "..", "examples")


def _run(script, *args):
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(_EX, script), *args],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


def test_gluon_mnist_converges():
    out = _run("gluon_mnist.py", "--epochs", "4", "--batches-per-epoch", "5",
               "--batch-size", "16", "--lr", "3e-3")
    losses = [float(m) for m in re.findall(r"loss=([0-9.]+)", out)]
    assert len(losses) == 4
    # the planted class pattern is learnable across fresh batches
    assert losses[-1] < losses[0] * 0.8, f"no convergence: {losses}"


def test_module_lenet_learns_train_subset():
    out = _run("module_lenet.py", "--epochs", "10", "--num-examples", "128",
               "--batch-size", "32")
    m = re.search(r"validation:.*?([0-9.]+)\)", out)
    assert m, out[-500:]
    acc = float(m.group(1))
    # val IS a train subset; memorizing 128 examples must beat the 0.1
    # chance floor decisively
    assert acc > 0.25, f"Module.fit failed to memorize: acc={acc}\n{out[-400:]}"


def test_distributed_train_loss_falls():
    out = _run("distributed_train.py", "--steps", "12", "--batch-size", "8")
    assert "done" in out
    losses = [float(m) for m in re.findall(r"loss=([0-9.]+)", out)]
    assert len(losses) >= 2
    assert losses[-1] < losses[0], f"dist loop did not learn: {losses}"


def test_distributed_train_tp():
    out = _run("distributed_train.py", "--steps", "4", "--batch-size", "8",
               "--tp", "2", "--force-cpu")
    assert "done" in out


def test_int8_inference_example():
    out = _run("int8_inference.py", "--steps", "25")
    assert "quantized 3/3" in out
    m = re.search(r"int8 accuracy:\s+([0-9.]+)", out)
    assert m and float(m.group(1)) > 0.9


def test_onnx_interchange_example(tmp_path):
    out = _run("onnx_interchange.py", "--out",
               str(tmp_path / "m.onnx"))
    assert "onnx interchange OK" in out


def test_long_context_attention_example():
    out = _run("long_context_attention.py", "--seq", "512")
    assert "long-context attention parity OK" in out


def test_resume_training_example(tmp_path):
    """Crash at step 4, rerun the same command, resume to step 8; the
    resumed run must pick up the committed step and the loss must keep
    falling across the interruption."""
    env = dict(os.environ)
    r1 = subprocess.run(
        [sys.executable, os.path.join(_EX, "resume_training.py"),
         "--steps", "8", "--ckpt-dir", str(tmp_path / "ck"),
         "--interrupt-at", "4"],
        capture_output=True, text=True, env=env, timeout=420)
    assert r1.returncode == 17, r1.stdout[-1500:] + r1.stderr[-1500:]
    assert "simulating crash" in r1.stdout
    l1 = [float(m) for m in re.findall(r"loss ([0-9.]+)", r1.stdout)]

    out = _run("resume_training.py", "--steps", "8",
               "--ckpt-dir", str(tmp_path / "ck"))
    assert "resumed from committed step 4" in out
    assert "done at step 8" in out
    l2 = [float(m) for m in re.findall(r"loss ([0-9.]+)", out)]
    assert l2[-1] < l1[0] * 0.5, (l1, l2)
