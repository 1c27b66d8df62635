"""Inference facade parity + multi-host bootstrap (VERDICT round-1 #10).

- export→predict parity: jit.save artifact served through the
  Config/Predictor API must reproduce the eager forward bitwise.
- multi-host: a real 2-process jax.distributed rendezvous through the
  PADDLE_* env contract (reference test_dist_base.py:783 runs the same
  2-worker gate with NCCL; here the coordinator is jax's distributed
  service on localhost and the collective runs over the CPU backend).
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle


class TestInferenceFacade:
    def _export(self, tmp_path):
        import paddle_tpu.nn as nn
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        path = str(tmp_path / "m" / "model")
        spec = [paddle.jit.InputSpec(shape=[2, 8], dtype="float32",
                                     name="feats")]
        paddle.jit.save(model, path, input_spec=spec)
        return model, path

    def test_export_predict_parity(self, tmp_path):
        model, path = self._export(tmp_path)
        x = np.random.RandomState(0).standard_normal((2, 8)).astype(np.float32)
        ref = np.asarray(model(paddle.to_tensor(x))._data)

        config = paddle.inference.Config(path)
        predictor = paddle.inference.create_predictor(config)
        names = predictor.get_input_names()
        assert names == ["feats"]
        predictor.get_input_handle("feats").copy_from_cpu(x)
        predictor.run()
        out_names = predictor.get_output_names()
        out = predictor.get_output_handle(out_names[0]).copy_to_cpu()
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_positional_run_and_clone(self, tmp_path):
        model, path = self._export(tmp_path)
        x = np.random.RandomState(1).standard_normal((2, 8)).astype(np.float32)
        ref = np.asarray(model(paddle.to_tensor(x))._data)
        predictor = paddle.inference.create_predictor(
            paddle.inference.Config(path))
        outs = predictor.run([x])
        np.testing.assert_allclose(outs[0], ref, rtol=1e-6, atol=1e-6)
        clone = predictor.clone()
        assert clone._layer is predictor._layer  # shares executable+weights
        outs2 = clone.run([x])
        np.testing.assert_allclose(outs2[0], ref, rtol=1e-6, atol=1e-6)

    def test_missing_model_raises(self, tmp_path):
        with pytest.raises(ValueError, match="not found"):
            paddle.inference.create_predictor(
                paddle.inference.Config(str(tmp_path / "nope")))


_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as dist_env

    dist_env.init_parallel_env()
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()           # both processes' cpu devices
    mesh = Mesh(np.array(devs), ("data",))
    # each process contributes its rank+1; global psum must see 1+2=3 per
    # device pair scaling — use make_array_from_callback so each host only
    # provides its own shard
    def cb(idx):
        return np.full((1,), float(jax.process_index() + 1), np.float32)
    arr = jax.make_array_from_callback(
        (len(devs),), NamedSharding(mesh, P("data")), lambda idx: np.full(
            (1,), float(rank + 1), np.float32))
    total = jax.jit(lambda a: jnp.sum(a),
                    out_shardings=NamedSharding(mesh, P()))(arr)
    print("RESULT", rank, float(np.asarray(total)), flush=True)
""")


@pytest.mark.skipif(os.environ.get("PADDLE_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost disabled")
def test_two_process_bootstrap(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PADDLE_TRAINERS_NUM": "2",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_MASTER": f"127.0.0.1:{port}",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env, cwd="/root/repo",
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host worker timed out")
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
        outs.append(out)
    for rank, out in enumerate(outs):
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT")]
        assert lines, f"no result from rank {rank}: {out}"
        _, r, total = lines[0].split()
        assert int(r) == rank
        # sum over 2 process-shards holding 1.0 and 2.0
        assert float(total) == pytest.approx(3.0)
