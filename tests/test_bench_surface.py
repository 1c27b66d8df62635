"""bench.py's entry and exit: the child measures on a TPU and fails without
one, the parent stays off JAX and returns the child's code, the tiny
geometry is a rehearsal that reports no metric, and chip peaks come from
one table keyed by the exact device kind."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench.py")


def _run_bench(*args, child=False, timeout=900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("_PADDLE_TPU_BENCH_CHILD", None)
    if child:
        env["_PADDLE_TPU_BENCH_CHILD"] = "1"
    return subprocess.run([sys.executable, BENCH, *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=ROOT)


@pytest.mark.slow
def test_bench_all_configs_rehearse_on_cpu():
    """Every config's tiny geometry runs end to end; each record says so
    and carries nothing under a device metric's name."""
    proc = _run_bench("--config", "all", "--rehearse", child=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(l) for l in proc.stdout.splitlines()
            if l.strip().startswith("{")]
    import bench
    assert [r["rehearsal"] for r in recs] == list(bench.CONFIGS)
    for r in recs:
        assert set(r) == {"rehearsal", "ran", "loss", "device"}, r
        assert r["device"]["platform"] == "cpu"


def test_child_without_a_tpu_fails_and_prints_no_record():
    proc = _run_bench("--config", "gpt2s", child=True, timeout=300)
    assert proc.returncode != 0
    assert "measures on a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_parent_returns_the_childs_code():
    """No probe, no retry, no record in place of a failure: what the child
    exits with is what the driver sees."""
    proc = _run_bench("--config", "gpt2s", timeout=300)
    assert proc.returncode == 1
    assert "measures on a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_parent_passes_the_childs_records_through():
    proc = _run_bench("--config", "mnist_lenet", "--rehearse", timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    [rec] = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert rec["rehearsal"] == "mnist_lenet" and rec["ran"] is True
    assert "value" not in rec and "metric" not in rec and "mfu" not in rec
    assert rec["device"]["platform"] == rec["device"]["kind"] == "cpu"


def test_parent_stops_the_child_at_its_limit(monkeypatch, tmp_path):
    """The limit stops the whole process group and reads as 124."""
    import bench
    sleeper = tmp_path / "sleeper.py"
    sleeper.write_text("import time\ntime.sleep(60)\n")
    monkeypatch.setattr(bench, "__file__", str(sleeper))
    assert bench._parent([], timeout=1.0) == 124


def test_parent_never_imports_jax():
    code = ("import sys, bench; sys.argv=['bench.py', '--help']\n"
            "try:\n    bench.main()\nexcept SystemExit:\n    pass\n"
            "assert 'jax' not in sys.modules, 'the parent touched jax'\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]


def test_peak_table_is_keyed_by_exact_device_kind():
    import bench
    assert bench._chip_peak("TPU v5 lite") == 197e12
    assert bench.CHIP_PEAK_BF16_FLOPS == {"TPU v5 lite": 197e12}


@pytest.mark.parametrize("kind", ["cpu", "TPU v5", "tpu v5 lite", "v5e", ""])
def test_unknown_device_kind_is_an_error(kind):
    """No substring match, no default, no None that turns into a null
    utilization: a chip that is not in the table is not measured."""
    import bench
    with pytest.raises(KeyError, match="no peak FLOP/s recorded"):
        bench._chip_peak(kind)


def test_default_device_kind_is_what_jax_reports():
    import bench
    with pytest.raises(KeyError, match="'cpu'"):
        bench._chip_peak()          # the test's own backend: not a chip


def test_analytic_flops_matches_6n_approximation():
    """_transformer_train_flops ≈ 6·N·tokens + attention term for gpt2s
    (Megatron/PaLM convention); guards the MFU denominator's honesty
    (VERDICT r2: XLA cost analysis undercounted scan models)."""
    import bench
    B, L = 16, 1024
    H, I, V, n = 768, 3072, 50304, 12
    got = bench._transformer_train_flops(B, L, n, H, I, V)
    # parameter count of the matmul path (QKVO 4H^2 + MLP 2HI per layer,
    # plus the tied head HV)
    N = n * (4 * H * H + 2 * H * I) + H * V
    attn = 3 * B * L * n * 4 * L * H          # train (3x) QK^T+PV term
    approx = 6 * N * B * L + attn
    assert abs(got - approx) / approx < 0.01, (got, approx)
    # MoE top-2 doubles only the expert-MLP term
    moe = bench._transformer_train_flops(B, L, n, H, I, V, moe_topk=2)
    assert moe - got == 3 * B * L * n * 4 * H * I
