"""Multi-host rehearsal on localhost (VERDICT r3 item 6): the WHOLE elastic
chain end-to-end in real separate processes —

  ``python -m paddle_tpu.distributed.launch --elastic_store tcp://...``
  → launcher hosts the native C++ TCP KV store (csrc/kv_store.cpp)
  → 2 worker processes rendezvous through it (ElasticManager heartbeats)
  → ``init_parallel_env`` brings up jax.distributed (Gloo CPU collectives)
  → a REAL dp-sharded train step (GSPMD mean-grad = cross-process psum)
  → dp-sharded checkpoint (distributed/checkpoint.py, each process writes
    only its shards)
  → rank 1 SIGKILLs itself mid-run (the elastic fault)
  → launcher --elastic_level 1 restarts the pod
  → both workers resume from the checkpoint and finish.

Reference flows: fleet/launch.py + launch_utils.py watch_local_trainers
(launcher), fleet/elastic/manager.py (membership/restart), distributed/
parallel.py init_parallel_env:71 (env contract), all exercised here against
the framework's own no-etcd store.

Pieces are unit-tested separately in test_store.py / test_launch_elastic.py /
test_checkpoint.py; this file is the integration proof that they compose.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

TRAINER = textwrap.dedent("""
    import os, signal
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import init_parallel_env, get_rank
    from paddle_tpu.distributed import checkpoint as dckpt
    from paddle_tpu.distributed.fleet.elastic import ElasticManager

    STORE = os.environ["PADDLE_ELASTIC_STORE"]   # exported by the launcher
    CKPT = os.environ["REHEARSAL_CKPT"]
    FLAG = os.environ["REHEARSAL_FLAG"]     # exists => the fault already fired
    TOTAL_STEPS = 6

    init_parallel_env()                     # jax.distributed from PADDLE_* env
    rank = get_rank()
    member = ElasticManager(STORE, rank=rank, heartbeat_interval=0.2,
                            lease_ttl=10.0)
    member.register()
    assert jax.process_count() == 2, jax.process_count()

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    repl = NamedSharding(mesh, P())
    row_sharded = NamedSharding(mesh, P("dp", None))
    ndev = jax.device_count()

    rng = np.random.RandomState(0)
    X = rng.randn(ndev * 2, 4).astype(np.float32)
    Y = X @ np.arange(8, dtype=np.float32).reshape(4, 2)
    rows = X.shape[0] // jax.process_count()
    x = jax.make_array_from_process_local_data(
        row_sharded, X[rank * rows:(rank + 1) * rows], global_shape=X.shape)
    y = jax.make_array_from_process_local_data(
        row_sharded, Y[rank * rows:(rank + 1) * rows], global_shape=Y.shape)

    w0 = jax.device_put(np.zeros((4, 2), np.float32), row_sharded)

    @jax.jit
    def train_step(w, x, y):
        def loss_fn(w):
            return jnp.mean((x @ w - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(w)
        return w - 0.05 * g, loss           # GSPMD inserts the grad psum

    start, w = 0, w0
    if os.path.isdir(CKPT) and os.listdir(CKPT):
        state = dckpt.load(CKPT, target={"w": w0, "step": 0},
                           shardings={"w": row_sharded, "step": None})
        start, w = int(state["step"]), state["w"]

    loss = None
    for step in range(start, TOTAL_STEPS):
        w, loss = train_step(w, x, y)
        dckpt.save({"w": w, "step": step + 1}, CKPT).wait()
        if rank == 1 and step == 2 and not os.path.exists(FLAG):
            open(FLAG, "w").close()         # flag first: kill exactly once
            os.kill(os.getpid(), signal.SIGKILL)

    member.stop()
    if rank == 0:
        print(f"REHEARSAL_DONE resumed_from={start} "
              f"loss={float(loss):.6f}", flush=True)
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.skipif(os.environ.get("PADDLE_TPU_SKIP_SUBPROC") == "1",
                    reason="subprocess tests disabled")
def test_launch_tcp_store_fault_restart_resume(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(TRAINER)
    store_port, master_port = _free_port(), _free_port()

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    env["REHEARSAL_CKPT"] = str(tmp_path / "ckpt")
    env["REHEARSAL_FLAG"] = str(tmp_path / "fault_fired")

    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--devices", "cpu", "--nproc_per_node", "2",
         "--master", f"127.0.0.1:{master_port}",
         "--elastic_level", "1", "--max_restarts", "2",
         "--elastic_store", f"tcp://127.0.0.1:{store_port}",
         "--log_dir", str(tmp_path / "log"), str(script)],
        capture_output=True, text=True, timeout=600, env=env,
        cwd="/root/repo")

    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    # the fault actually fired, the pod restarted, and the relaunched run
    # resumed from the step-3 checkpoint rather than from scratch
    assert os.path.exists(env["REHEARSAL_FLAG"])
    assert "elastic restart 1/" in r.stderr, r.stderr[-2000:]
    assert "REHEARSAL_DONE resumed_from=3" in r.stdout, r.stdout[-2000:]
    # training really progressed: 6 SGD steps on y = x @ w* from w=0 must cut
    # the loss well below the step-0 value (~70 for this fixed seed; 6 steps
    # at lr 0.05 land ~40)
    loss = float(r.stdout.split("loss=")[1].split()[0])
    assert 0.0 < loss < 50.0, loss
