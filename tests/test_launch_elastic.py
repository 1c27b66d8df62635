"""Launcher + elastic manager tests (reference: launch_utils watch loop and
fleet/elastic/manager.py heartbeat/membership semantics)."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.distributed.fleet.elastic import (ELASTIC_EXIT_CODE,
                                                  RESCALE_EXIT_CODE,
                                                  ElasticManager)


class TestElasticManager:
    def test_heartbeat_and_membership(self, tmp_path):
        m0 = ElasticManager(str(tmp_path), rank=0, heartbeat_interval=0.1,
                            lease_ttl=1.0).register()
        m1 = ElasticManager(str(tmp_path), rank=1, heartbeat_interval=0.1,
                            lease_ttl=1.0).register()
        assert m0.alive_ranks() == [0, 1]
        assert m0.exit_code() is None  # steady state
        m1.stop()
        time.sleep(0.2)
        assert m0.alive_ranks() == [0]
        # fault-tolerance level: peer loss → restart code
        assert m0.exit_code() == ELASTIC_EXIT_CODE
        m0.stop()

    def test_rescale_code_in_elastic_mode(self, tmp_path):
        m0 = ElasticManager(str(tmp_path), rank=0, np_range="1:4",
                            heartbeat_interval=0.1, lease_ttl=5.0).register()
        assert m0.exit_code() is None
        # a new host joins → world grew → rescale
        m2 = ElasticManager(str(tmp_path), rank=2, np_range="1:4",
                            heartbeat_interval=0.1, lease_ttl=5.0).register()
        assert m0.exit_code() == RESCALE_EXIT_CODE
        m0.stop(); m2.stop()

    def test_lease_expiry(self, tmp_path):
        m = ElasticManager(str(tmp_path), rank=0, heartbeat_interval=10,
                           lease_ttl=0.2)
        m._beat()
        assert m.alive_ranks() == [0]
        time.sleep(0.3)
        assert m.alive_ranks() == []


@pytest.mark.skipif(os.environ.get("PADDLE_TPU_SKIP_SUBPROC") == "1",
                    reason="subprocess tests disabled")
class TestLauncher:
    def _run_launch(self, tmp_path, script_body, extra=(), timeout=120):
        script = tmp_path / "train.py"
        script.write_text(textwrap.dedent(script_body))
        env = dict(os.environ)
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--log_dir", str(tmp_path / "log"), *extra, str(script)],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd="/root/repo")

    def test_single_proc_env_contract(self, tmp_path):
        r = self._run_launch(tmp_path, """
            import os
            assert os.environ["PADDLE_TRAINER_ID"] == "0"
            assert os.environ["PADDLE_TRAINERS_NUM"] == "1"
            print("ENV_OK")
        """)
        assert r.returncode == 0 and "ENV_OK" in r.stdout, r.stderr

    def test_failure_propagates(self, tmp_path):
        r = self._run_launch(tmp_path, "import sys; sys.exit(7)")
        assert r.returncode == 7

    def test_elastic_restart_then_success(self, tmp_path):
        # first run exits 101 (elastic restart), relaunch succeeds
        r = self._run_launch(tmp_path, """
            import os, sys
            flag = os.path.join(os.path.dirname(__file__), "ran_once")
            if not os.path.exists(flag):
                open(flag, "w").close()
                sys.exit(101)
            print("RESUMED")
        """, extra=["--max_restarts", "2"])
        assert r.returncode == 0 and "RESUMED" in r.stdout, r.stderr

    def test_fault_injection_sigkill_restarts_at_level1(self, tmp_path):
        """Fault-tolerant level 1 (reference elastic manager.py:178): a
        trainer killed with SIGKILL (rc=-9, no exit-code protocol possible)
        restarts the pod; the relaunched run succeeds."""
        r = self._run_launch(tmp_path, """
            import os, signal
            flag = os.path.join(os.path.dirname(__file__), "killed_once")
            if not os.path.exists(flag):
                open(flag, "w").close()
                os.kill(os.getpid(), signal.SIGKILL)
            print("SURVIVED")
        """, extra=["--elastic_level", "1", "--max_restarts", "2"])
        assert r.returncode == 0 and "SURVIVED" in r.stdout, (r.stdout, r.stderr)

    def test_sigkill_without_level1_fails(self, tmp_path):
        r = self._run_launch(tmp_path, """
            import os, signal
            os.kill(os.getpid(), signal.SIGKILL)
        """)
        assert r.returncode != 0

    def test_level1_crash_loop_propagates_real_code(self, tmp_path):
        r = self._run_launch(tmp_path, "import sys; sys.exit(7)",
                             extra=["--elastic_level", "1",
                                    "--max_restarts", "2"])
        assert r.returncode == 7, r.returncode  # not 101


class TestOneProcessPerChip:
    """A chip belongs to one process at a time, so on a TPU host the
    launcher starts ONE process; several are for the CPU simulation only."""

    def test_refuses_two_processes_off_the_cpu_simulation(self, capsys):
        from paddle_tpu.distributed.launch import _parse_args
        with pytest.raises(SystemExit) as e:
            _parse_args(["--nproc_per_node", "2", "train.py"])
        assert e.value.code == 2
        assert "--devices cpu" in capsys.readouterr().err

    def test_refusal_reaches_the_command_line(self, tmp_path):
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--log_dir", str(tmp_path / "log"), "--nproc_per_node", "2",
             str(tmp_path / "never_run.py")],
            capture_output=True, text=True, timeout=120, cwd="/root/repo")
        assert r.returncode == 2 and "one process drives" in r.stderr
        assert not (tmp_path / "log").exists()      # refused before any work

    def test_cpu_simulation_keeps_several_processes(self):
        from paddle_tpu.distributed.launch import _child_env, _parse_args
        args = _parse_args(["--devices", "cpu", "--nproc_per_node", "2",
                            "train.py"])
        assert args.nproc_per_node == 2
        env = _child_env(args, 1, 2, 2)
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["PADDLE_TRAINER_ID"] == "1"
        assert "host_platform_device_count" in env["XLA_FLAGS"]

    def test_one_process_is_the_default_everywhere(self):
        from paddle_tpu.distributed.launch import _parse_args
        assert _parse_args(["train.py"]).nproc_per_node == 1


class TestTCPStoreLaunch:
    def test_launcher_hosts_tcp_store_end_to_end(self, tmp_path):
        """--elastic_store tcp://127.0.0.1:PORT: the launcher binds the
        native store server in-process and the trainer registers + reads
        membership through it (the no-etcd multi-host path, ≙ reference
        manager.py etcd flows)."""
        import socket
        import subprocess
        import sys
        import textwrap

        with socket.socket() as s:  # reserve a free port number
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]

        script = tmp_path / "train.py"
        script.write_text(textwrap.dedent(f"""
            from paddle_tpu.distributed.fleet.elastic import ElasticManager
            m = ElasticManager("tcp://127.0.0.1:{port}", rank=0,
                               heartbeat_interval=0.1, lease_ttl=5.0)
            m.register()
            assert m.alive_ranks() == [0], m.alive_ranks()
            m.stop()
            print("TCP_STORE_OK")
        """))
        env = dict(os.environ)
        env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--log_dir", str(tmp_path / "log"),
             "--elastic_store", f"tcp://127.0.0.1:{port}", str(script)],
            capture_output=True, text=True, timeout=120, env=env,
            cwd="/root/repo")
        assert r.returncode == 0 and "TCP_STORE_OK" in r.stdout, \
            (r.stdout, r.stderr)
