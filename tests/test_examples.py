"""The examples/ scripts must stay runnable — they are the first thing a
reference user tries.  Each runs as a subprocess on the CPU backend with
tiny step counts."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(ROOT, "examples")


def _run(script, *args, env_extra=None, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.run([sys.executable, os.path.join(EXAMPLES, script)]
                          + list(args),
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


@pytest.mark.slow
class TestExamples:
    def test_mnist(self):
        out = _run("train_mnist.py")
        assert "loss" in out

    def test_quantize_ptq(self):
        out = _run("quantize_ptq.py")
        assert "int8 accuracy" in out

    def test_bert(self):
        out = _run("finetune_bert.py")
        assert "step 9" in out

    def test_gpt_hybrid_2x2x2(self):
        out = _run("train_gpt_hybrid.py", "--dp", "2", "--mp", "2",
                   "--pp", "2", "--steps", "2",
                   env_extra={"XLA_FLAGS":
                              "--xla_force_host_platform_device_count=8"})
        assert "step 1" in out

    def test_gpt_hybrid_zero2(self):
        out = _run("train_gpt_hybrid.py", "--dp", "4", "--zero", "2",
                   "--steps", "2",
                   env_extra={"XLA_FLAGS":
                              "--xla_force_host_platform_device_count=8"})
        assert "step 1" in out

    def test_generate_gpt(self):
        out = _run("generate_gpt.py", "--max_new_tokens", "6",
                   "--num_beams", "2")
        assert "GENERATION_OK" in out

    def test_serve_bucketed(self):
        out = _run("serve_bucketed.py")
        assert "SERVE_OK" in out

    def test_serve_continuous(self):
        out = _run("serve_continuous.py", "--int8", "--ticks_per_sync", "2")
        assert "6 requests, 112 tokens" in out
