"""chip_smoke.py, driven the only way it can be here: ``--rehearse`` (tiny
size, virtual CPU devices, Pallas kernels interpreted).  That proves the
script — every phase line, what a failing phase does, what happens with no
TPU — and nothing about the chip; the chip run is the builder's and the
driver's.

Each rehearsal is a subprocess of a quarter of a minute, so it is run once
and shared: under xdist through a file in the run's common temp directory,
behind a lock, since the tests of one module land on several workers.
"""

import fcntl
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def run_python(argv, cache_dir, cwd=ROOT, pythonpath=True):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)        # the script asks for its own devices
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    if not pythonpath:
        env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return {"rc": proc.returncode, "lines": lines, "stdout": proc.stdout,
            "stderr": proc.stderr[-3000:], "cache_dir": str(cache_dir)}


def once(tmp_path_factory, name, argv):
    """The result of one rehearsal, made by whichever worker gets here
    first."""
    base = tmp_path_factory.getbasetemp()
    shared = base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base
    result = shared / f"chip_smoke_{name}.json"
    with open(f"{result}.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not result.exists():
            run = run_python([SCRIPT, *argv], shared / f"cache_{name}")
            result.write_text(json.dumps(run))
        return json.loads(result.read_text())


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    return once(tmp_path_factory, "one", ["--rehearse"])


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    return once(tmp_path_factory, "four", ["--rehearse", "--chips", "4"])


def phase(run, name):
    found = [ln for ln in run["lines"] if ln.get("phase") == name]
    assert len(found) == 1, (name, run["stdout"], run["stderr"])
    return found[0]


def test_rehearsal_exits_zero(one_chip):
    assert one_chip["rc"] == 0, one_chip["stderr"]


def test_start_line_names_device_and_the_cache_placed_from_outside(one_chip):
    start = phase(one_chip, "start")
    assert start["rehearsal"] is True
    assert start["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # JAX_COMPILATION_CACHE_DIR was set: the script set no other
    assert start["compile_cache"] == one_chip["cache_dir"]
    assert os.listdir(one_chip["cache_dir"])


def test_kernels_phase_checks_every_kernel_against_its_oracle(one_chip):
    line = phase(one_chip, "kernels")
    assert line["ok"] is True
    cases = {c["case"]: c for c in line["cases"]}
    assert sorted(cases) == ["flash_bwd", "flash_fwd", "fused_ce",
                             "paged_decode", "ragged_paged",
                             "ragged_paged_int8"]
    for case in cases.values():
        assert 0 <= case["max_err"] <= case["tol"], case
        # what shows a kernel in the program exists only on the chip
        assert "kernels_in_program" not in case


def test_train_phase_takes_five_steps_and_the_loss_falls(one_chip):
    line = phase(one_chip, "train")
    losses = line["losses"]
    assert line["ok"] is True and len(losses) == 5
    assert all(x == x and abs(x) != float("inf") for x in losses)
    assert losses[-1] < losses[0]
    # a rehearsal carries no value under a device metric's name
    assert "step_s" not in line and "tokens_per_step" not in line


def test_serve_phase_serves_eight_requests_with_no_compile(one_chip):
    line = phase(one_chip, "serve")
    assert line["ok"] is True
    assert line["requests"] == 8
    assert line["tokens"] == 28                 # TINY's new_tokens, summed
    assert line["warmup_programs"] >= 1
    assert line["engine_compiles_after_warmup"] == 0
    assert line["mixed_steps"] >= 1             # prefill and decode shared
    assert line["first_token_logits_max_err"] <= line["logits_tol"]
    assert "serve_s" not in line


def test_every_phase_reports_cache_hits_and_misses(one_chip):
    for name in ("kernels", "train", "serve"):
        cache = phase(one_chip, name)["cache"]
        assert set(cache) == {"hits", "misses"}
        assert cache["hits"] + cache["misses"] > 0, name


def test_rehearsal_never_prints_the_contract_line(one_chip):
    last = one_chip["lines"][-1]
    assert last == {"rehearsal": True, "phases": ["kernels", "train",
                                                  "serve"],
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert not any(ln.get("ok") and "phase" not in ln
                   for ln in one_chip["lines"])


def test_four_chips_runs_the_sharded_phase_and_no_other(four_chips):
    assert four_chips["rc"] == 0, four_chips["stderr"]
    phases = [ln["phase"] for ln in four_chips["lines"] if "phase" in ln]
    assert phases == ["start", "sharded"]
    assert four_chips["lines"][-1] == {
        "rehearsal": True, "phases": ["sharded"],
        "device": {"platform": "cpu", "kind": "cpu", "count": 4}}


def test_four_chips_losses_match_one_device(four_chips):
    line = phase(four_chips, "sharded")
    layouts = line["layouts"]
    assert sorted(layouts) == ["dp2_mp2", "one_device", "zero3_x4"]
    for name in ("dp2_mp2", "zero3_x4"):
        assert layouts[name]["max_loss_gap"] <= line["loss_tol"]
        assert len(layouts[name]["losses"]) == len(
            layouts["one_device"]["losses"]) == 2


def test_four_chips_state_is_spread_over_the_devices(four_chips):
    layouts = phase(four_chips, "sharded")["layouts"]
    one = layouts["one_device"]["state_bytes_per_device"]
    assert sorted(one)[:3] == [0, 0, 0] and max(one) > 0
    for name, share in (("dp2_mp2", 0.5), ("zero3_x4", 0.25)):
        held = layouts[name]["state_bytes_per_device"]
        assert len(held) == 4 and min(held) > 0
        assert max(held) <= (share + 0.1) * max(one), (name, held, one)
    # and each layout printed its own line as it finished
    assert [ln["layout"] for ln in four_chips["lines"] if "layout" in ln] \
        == ["one_device", "dp2_mp2", "zero3_x4"]


def test_a_failing_phase_fails_the_script(tmp_path):
    """The oracle is made wrong from outside, in the child: the kernels
    phase must raise, nothing after it may run, no last line."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import paddle_tpu.ops.attention as A\n"
        "real = A.dense_attention\n"
        "A.dense_attention = lambda *a, **k: real(*a, **k) * 0.5\n"
        "import chip_smoke\n"
        "sys.exit(chip_smoke.main(['--rehearse']))\n")
    run = run_python(["-c", code], tmp_path / "cache")
    assert run["rc"] != 0
    assert "SmokeFailure" in run["stderr"] and "flash_fwd" in run["stderr"]
    assert [ln.get("phase") for ln in run["lines"]] == ["start"]


def test_without_a_tpu_and_without_rehearse_it_fails_and_prints_nothing(
        tmp_path):
    run = run_python([SCRIPT], tmp_path / "cache")
    assert run["rc"] != 0
    assert run["stdout"] == ""
    assert "needs a TPU" in run["stderr"]
    run = run_python([SCRIPT, "--chips", "4"], tmp_path / "cache")
    assert run["rc"] != 0 and run["stdout"] == ""


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script is the repo's entry points driven, not a copy of them."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    run = run_python(["chip_smoke.py", "--rehearse"], tmp_path / "cache",
                     cwd=tmp_path, pythonpath=False)
    assert run["rc"] != 0
    assert run["lines"] == []
    assert "paddle_tpu" in run["stderr"]
