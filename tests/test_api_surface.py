"""Tests for the advertised API modules (VERDICT round-1 #9: every name in
``_LAZY`` must import and carry real behavior).

Oracles are numpy/brute-force recomputations (reference op_test.py style).
"""

import itertools
import os

import numpy as np
import pytest

import paddle_tpu as paddle


def test_all_lazy_modules_import():
    for name in paddle._LAZY:
        mod = getattr(paddle, name)
        assert mod is not None, name


# ---------------------------------------------------------------------------
# fft / signal
# ---------------------------------------------------------------------------

class TestFFT:
    def test_fft_matches_numpy(self):
        x = np.random.RandomState(0).standard_normal((4, 32)).astype(np.float32)
        out = paddle.fft.fft(paddle.to_tensor(x))
        np.testing.assert_allclose(np.asarray(out._data), np.fft.fft(x),
                                   rtol=1e-4, atol=1e-4)

    def test_rfft_irfft_roundtrip(self):
        x = np.random.RandomState(1).standard_normal((8, 64)).astype(np.float32)
        spec = paddle.fft.rfft(paddle.to_tensor(x))
        back = paddle.fft.irfft(spec, n=64)
        np.testing.assert_allclose(np.asarray(back._data), x, atol=1e-4)

    def test_norm_ortho_and_shift(self):
        x = np.random.RandomState(2).standard_normal((16,)).astype(np.float32)
        o = paddle.fft.fft(paddle.to_tensor(x), norm="ortho")
        np.testing.assert_allclose(np.asarray(o._data),
                                   np.fft.fft(x, norm="ortho"), rtol=1e-4,
                                   atol=1e-4)
        s = paddle.fft.fftshift(paddle.to_tensor(x))
        np.testing.assert_allclose(np.asarray(s._data), np.fft.fftshift(x))
        with pytest.raises(ValueError):
            paddle.fft.fft(paddle.to_tensor(x), norm="bogus")

    def test_fft2(self):
        x = np.random.RandomState(3).standard_normal((4, 8, 8)).astype(np.float32)
        out = paddle.fft.fft2(paddle.to_tensor(x))
        np.testing.assert_allclose(np.asarray(out._data), np.fft.fft2(x),
                                   rtol=1e-3, atol=1e-3)

    def test_hfft2_matches_numpy_composition(self):
        # oracle: c2c over the leading axis first, then hermitian c2r over
        # the last (the order the reference's fftn_c2r kernel uses)
        rng = np.random.RandomState(9)
        x = (rng.standard_normal((4, 5)) + 1j * rng.standard_normal((4, 5))
             ).astype(np.complex64)
        out = paddle.fft.hfft2(paddle.to_tensor(x))
        ref = np.fft.hfft(np.fft.fft(x, axis=0), axis=-1)
        np.testing.assert_allclose(np.asarray(out._data), ref, rtol=1e-3,
                                   atol=1e-3)


class TestSignal:
    def test_frame_matches_oracle(self):
        x = np.arange(16, dtype=np.float32)
        out = paddle.signal.frame(paddle.to_tensor(x), frame_length=4,
                                  hop_length=2, axis=0)
        ref = np.stack([x[i:i + 4] for i in range(0, 13, 2)])
        np.testing.assert_array_equal(np.asarray(out._data), ref)

    def test_overlap_add_is_frame_adjoint(self):
        rng = np.random.RandomState(0)
        fr = rng.standard_normal((7, 4)).astype(np.float32)  # (F, L) axis=0
        out = paddle.signal.overlap_add(paddle.to_tensor(fr), hop_length=2,
                                        axis=0)
        ref = np.zeros(6 * 2 + 4, np.float32)
        for i in range(7):
            ref[i * 2:i * 2 + 4] += fr[i]
        np.testing.assert_allclose(np.asarray(out._data), ref, atol=1e-6)

    def test_stft_onesided_complex_raises(self):
        x = np.zeros((256,), np.complex64)
        with pytest.raises(ValueError, match="onesided"):
            paddle.signal.stft(paddle.to_tensor(x), n_fft=64)

    def test_stft_istft_roundtrip(self):
        rng = np.random.RandomState(4)
        x = rng.standard_normal((2, 512)).astype(np.float32)
        win = np.hanning(128).astype(np.float32)
        spec = paddle.signal.stft(paddle.to_tensor(x), n_fft=128, hop_length=32,
                                  window=paddle.to_tensor(win))
        # padded len 640 → frames = 1 + (640-128)//32 = 17; bins = 128//2+1
        assert np.asarray(spec._data).shape == (2, 65, 17)
        back = paddle.signal.istft(spec, n_fft=128, hop_length=32,
                                   window=paddle.to_tensor(win), length=512)
        np.testing.assert_allclose(np.asarray(back._data), x, atol=1e-3)


# ---------------------------------------------------------------------------
# distribution
# ---------------------------------------------------------------------------

class TestDistribution:
    def test_normal_log_prob_and_kl(self):
        d = paddle.distribution.Normal(loc=1.0, scale=2.0)
        v = paddle.to_tensor(np.array([0.0, 1.0, 3.0], np.float32))
        lp = np.asarray(d.log_prob(v)._data)
        ref = -((np.array([0.0, 1.0, 3.0]) - 1.0) ** 2) / 8.0 \
            - np.log(2.0) - 0.5 * np.log(2 * np.pi)
        np.testing.assert_allclose(lp, ref, rtol=1e-5)
        q = paddle.distribution.Normal(loc=0.0, scale=1.0)
        kl = float(np.asarray(paddle.distribution.kl_divergence(d, q)._data))
        ref_kl = np.log(1.0 / 2.0) + (4.0 + 1.0) / 2.0 - 0.5
        np.testing.assert_allclose(kl, ref_kl, rtol=1e-5)

    def test_sampling_moments(self):
        paddle.seed(7)
        d = paddle.distribution.Normal(loc=3.0, scale=0.5)
        s = np.asarray(d.sample([20000])._data)
        assert abs(s.mean() - 3.0) < 0.05 and abs(s.std() - 0.5) < 0.05
        u = paddle.distribution.Uniform(low=-1.0, high=1.0)
        su = np.asarray(u.sample([20000])._data)
        assert su.min() >= -1.0 and su.max() < 1.0 and abs(su.mean()) < 0.05

    def test_categorical(self):
        paddle.seed(8)
        logits = np.log(np.array([0.2, 0.3, 0.5], np.float32))
        d = paddle.distribution.Categorical(logits)
        s = np.asarray(d.sample([8000])._data)
        freq = np.bincount(s, minlength=3) / 8000.0
        np.testing.assert_allclose(freq, [0.2, 0.3, 0.5], atol=0.03)
        lp = np.asarray(d.log_prob(paddle.to_tensor(np.array([2]))). _data)
        np.testing.assert_allclose(lp, np.log(0.5), rtol=1e-4)
        ent = float(np.asarray(d.entropy()._data))
        np.testing.assert_allclose(
            ent, -(0.2 * np.log(0.2) + 0.3 * np.log(0.3) + 0.5 * np.log(0.5)),
            rtol=1e-4)


# ---------------------------------------------------------------------------
# sparse
# ---------------------------------------------------------------------------

class TestSparse:
    def test_coo_roundtrip_and_matmul(self):
        ind = np.array([[0, 0, 1, 2], [0, 2, 1, 0]])
        val = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
        s = paddle.sparse.sparse_coo_tensor(ind, val, [3, 3])
        dense = np.zeros((3, 3), np.float32)
        dense[ind[0], ind[1]] = val
        np.testing.assert_array_equal(np.asarray(s.to_dense()._data), dense)
        assert s.nnz() == 4
        rhs = np.random.RandomState(0).standard_normal((3, 2)).astype(np.float32)
        out = paddle.sparse.matmul(s, paddle.to_tensor(rhs))
        np.testing.assert_allclose(np.asarray(out._data), dense @ rhs,
                                   rtol=1e-5, atol=1e-5)

    def test_csr_and_unary(self):
        crows = np.array([0, 2, 3, 4])
        cols = np.array([0, 2, 1, 0])
        vals = np.array([-1.0, 2.0, -3.0, 4.0], np.float32)
        s = paddle.sparse.sparse_csr_tensor(crows, cols, vals, [3, 3])
        r = paddle.sparse.relu(s)
        np.testing.assert_array_equal(
            np.asarray(r.values()._data), [0.0, 2.0, 0.0, 4.0])
        dense = np.asarray(s.to_dense()._data)
        ref = np.zeros((3, 3), np.float32)
        ref[[0, 0, 1, 2], [0, 2, 1, 0]] = vals
        np.testing.assert_array_equal(dense, ref)


# ---------------------------------------------------------------------------
# autograd: PyLayer + functional transforms
# ---------------------------------------------------------------------------

class TestPyLayer:
    def test_custom_backward_is_used(self):
        from paddle_tpu.autograd import PyLayer

        class ScaledTanh(PyLayer):
            @staticmethod
            def forward(ctx, x):
                y = paddle.tanh(x)
                ctx.save_for_backward(y)
                return y

            @staticmethod
            def backward(ctx, dy):
                (y,) = ctx.saved_tensor()
                return dy * (1 - y * y) * 10.0   # deliberately 10x

        x = paddle.to_tensor(np.array([0.3, -0.7], np.float32),
                             stop_gradient=False)
        out = ScaledTanh.apply(x)
        out.backward(paddle.to_tensor(np.ones(2, np.float32)))
        ref = (1 - np.tanh([0.3, -0.7]) ** 2) * 10.0
        np.testing.assert_allclose(np.asarray(x.grad._data), ref, rtol=1e-5)

    def test_multi_input_output(self):
        from paddle_tpu.autograd import PyLayer

        class MulAdd(PyLayer):
            @staticmethod
            def forward(ctx, a, b):
                ctx.save_for_backward(a, b)
                return a * b, a + b

            @staticmethod
            def backward(ctx, g1, g2):
                a, b = ctx.saved_tensor()
                return g1 * b + g2, g1 * a + g2

        a = paddle.to_tensor(np.array([2.0], np.float32), stop_gradient=False)
        b = paddle.to_tensor(np.array([3.0], np.float32), stop_gradient=False)
        p, s = MulAdd.apply(a, b)
        (p + 2 * s).backward()
        np.testing.assert_allclose(np.asarray(a.grad._data), [3.0 + 2.0])
        np.testing.assert_allclose(np.asarray(b.grad._data), [2.0 + 2.0])


class TestAutogradFunctional:
    def test_multi_root_backward(self):
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                             stop_gradient=False)
        y1 = (x * x).sum()
        y2 = (3 * x).sum()
        paddle.autograd.backward([y1, y2])
        np.testing.assert_allclose(np.asarray(x.grad._data),
                                   2 * np.array([1.0, 2.0]) + 3.0)

    def test_jacobian_hessian(self):
        x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        jac = paddle.autograd.jacobian(lambda t: t * t, x)
        np.testing.assert_allclose(np.asarray(jac._data),
                                   np.diag([2.0, 4.0, 6.0]), rtol=1e-5)
        hes = paddle.autograd.hessian(lambda t: (t * t).sum(), x)
        np.testing.assert_allclose(np.asarray(hes._data), 2 * np.eye(3),
                                   rtol=1e-5)

    def test_vjp_jvp(self):
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        v = paddle.to_tensor(np.array([1.0, 0.0], np.float32))
        out, cot = paddle.autograd.vjp(lambda t: t * t, x, v)
        np.testing.assert_allclose(np.asarray(cot._data), [2.0, 0.0])
        out, tan = paddle.autograd.jvp(lambda t: t * t, x, v)
        np.testing.assert_allclose(np.asarray(tan._data), [2.0, 0.0])


# ---------------------------------------------------------------------------
# static facade, device, callbacks
# ---------------------------------------------------------------------------

class TestStatic:
    def test_data_and_accuracy(self):
        spec = paddle.static.data("x", [None, 4], "float32")
        assert spec.shape[-1] == 4
        pred = np.array([[0.1, 0.9], [0.8, 0.2]], np.float32)
        lbl = np.array([1, 1])
        acc = paddle.static.accuracy(paddle.to_tensor(pred),
                                     paddle.to_tensor(lbl))
        np.testing.assert_allclose(float(np.asarray(acc._data)), 0.5)

    def test_ema_apply_restore(self):
        import paddle_tpu.nn as nn
        paddle.seed(0)
        lin = nn.Linear(2, 2)
        ema = paddle.static.ExponentialMovingAverage(decay=0.5)
        w0 = np.array(lin.weight._data)
        ema.update(lin.parameters())
        lin.weight._data = lin.weight._data + 1.0
        ema.update()
        with ema.apply():
            applied = np.array(lin.weight._data)
        restored = np.array(lin.weight._data)
        np.testing.assert_allclose(restored, w0 + 1.0, rtol=1e-5)
        assert not np.allclose(applied, restored)

    def test_program_guard_and_executor(self):
        prog = paddle.static.Program()
        with paddle.static.program_guard(prog):
            assert paddle.static.default_main_program() is prog
        exe = paddle.static.Executor()
        out = exe.run(lambda a: a + 1,
                      feed={"x": np.zeros((2,), np.float32)})
        np.testing.assert_array_equal(out[0], np.ones((2,), np.float32))

    def test_append_backward_raises(self):
        with pytest.raises(RuntimeError):
            paddle.static.append_backward(None)


class TestDevice:
    def test_local_devices_raises_for_a_platform_that_is_not_there(self):
        """Never a quiet move to the default platform: code that asked for
        a TPU and got the CPU would measure the wrong machine."""
        from paddle_tpu.core.device import local_devices
        with pytest.raises(RuntimeError, match="tpu"):
            local_devices("tpu")
        assert [d.platform for d in local_devices("cpu")] == ["cpu"] * 8
        import jax
        assert local_devices() == jax.devices()

    def test_device_api(self):
        dev = paddle.device.get_device()
        assert ":" in dev
        assert paddle.device.cuda.device_count() == 0
        assert paddle.device.is_compiled_with_cuda() is False
        assert paddle.device.get_cudnn_version() is None
        types = paddle.device.get_all_device_type()
        assert "cpu" in types

    def test_callbacks_module(self):
        assert paddle.callbacks.EarlyStopping is not None
        assert paddle.callbacks.ModelCheckpoint is not None


# ---------------------------------------------------------------------------
# text: viterbi + datasets
# ---------------------------------------------------------------------------

def _viterbi_oracle(pot, trans, lengths, bos_eos):
    B, L, N = pot.shape
    scores, paths = [], []
    for b in range(B):
        ln = int(lengths[b])
        best, arg = -1e30, None
        for path in itertools.product(range(N), repeat=ln):
            s = pot[b, 0, path[0]]
            if bos_eos:
                s += trans[-1, path[0]]
            for t in range(1, ln):
                s += trans[path[t - 1], path[t]] + pot[b, t, path[t]]
            if bos_eos:
                s += trans[path[ln - 1], -2]
            if s > best:
                best, arg = s, path
        scores.append(best)
        paths.append(list(arg) + [0] * (int(lengths.max()) - ln))
    return np.array(scores, np.float32), np.array(paths)


class TestViterbi:
    @pytest.mark.parametrize("bos_eos", [False, True])
    def test_matches_bruteforce(self, bos_eos):
        rng = np.random.RandomState(5)
        B, L, N = 3, 5, 4
        pot = rng.standard_normal((B, L, N)).astype(np.float32)
        trans = rng.standard_normal((N, N)).astype(np.float32)
        lengths = np.array([5, 3, 1])
        scores, path = paddle.text.viterbi_decode(
            paddle.to_tensor(pot), paddle.to_tensor(trans),
            paddle.to_tensor(lengths), include_bos_eos_tag=bos_eos)
        ref_s, ref_p = _viterbi_oracle(pot, trans, lengths, bos_eos)
        np.testing.assert_allclose(np.asarray(scores._data), ref_s, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(path._data), ref_p)

    def test_decoder_layer(self):
        rng = np.random.RandomState(6)
        pot = rng.standard_normal((2, 4, 3)).astype(np.float32)
        trans = rng.standard_normal((3, 3)).astype(np.float32)
        dec = paddle.text.ViterbiDecoder(paddle.to_tensor(trans),
                                         include_bos_eos_tag=False)
        scores, path = dec(paddle.to_tensor(pot),
                           paddle.to_tensor(np.array([4, 4])))
        assert np.asarray(path._data).shape == (2, 4)


class TestTextDatasets:
    def test_uci_housing(self, tmp_path):
        rng = np.random.RandomState(0)
        rows = rng.rand(50, 14).astype(np.float32)
        f = tmp_path / "housing.data"
        with open(f, "w") as fh:
            for r in rows:
                fh.write(" ".join(f"{v:.6f}" for v in r) + "\n")
        train = paddle.text.UCIHousing(data_file=str(f), mode="train")
        test = paddle.text.UCIHousing(data_file=str(f), mode="test")
        assert len(train) == 40 and len(test) == 10
        x, y = train[0]
        assert x.shape == (13,) and y.shape == (1,)

    def test_imikolov_ngram(self, tmp_path):
        f = tmp_path / "ptb.train.txt"
        f.write_text("the cat sat on the mat\nthe dog sat on the log\n")
        ds = paddle.text.Imikolov(data_file=str(f), data_type="NGRAM",
                                  window_size=3, mode="train",
                                  min_word_freq=1)
        assert len(ds) > 0
        assert ds[0].shape == (3,)

    def test_missing_file_raises(self):
        with pytest.raises(ValueError, match="data_file"):
            paddle.text.Imdb(data_file=None)


# ---------------------------------------------------------------------------
# incubate: LookAhead / ModelAverage / auto_checkpoint; L1Decay
# ---------------------------------------------------------------------------

class TestIncubate:
    def test_lookahead_sync_every_k(self):
        import paddle_tpu.nn as nn
        paddle.seed(0)
        lin = nn.Linear(2, 2)
        sgd = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        la = paddle.incubate.LookAhead(sgd, alpha=0.5, k=2)
        w0 = np.array(lin.weight._data)
        x = paddle.to_tensor(np.ones((4, 2), np.float32))
        for i in range(2):
            loss = (lin(x) ** 2).mean()
            loss.backward()
            la.step()
            la.clear_grad()
        # after k=2 steps, weights = slow + 0.5*(fast - slow): strictly
        # between the initial (slow) and what plain SGD would give (fast)
        w2 = np.array(lin.weight._data)
        assert not np.allclose(w2, w0)

    def test_model_average(self):
        import paddle_tpu.nn as nn
        paddle.seed(0)
        lin = nn.Linear(2, 2)
        ma = paddle.incubate.ModelAverage(0.5, parameters=lin.parameters(),
                                          min_average_window=100)
        vals = []
        for i in range(3):
            lin.weight._data = lin.weight._data + 1.0
            ma.step()
            vals.append(np.array(lin.weight._data))
        cur = np.array(lin.weight._data)
        with ma.apply():
            avg = np.array(lin.weight._data)
        np.testing.assert_allclose(avg, np.mean(vals, axis=0), rtol=1e-5)
        np.testing.assert_allclose(np.array(lin.weight._data), cur)

    def test_model_average_across_window_restart(self):
        """After a window restart the average must stay the true mean of the
        folded samples (round-2 review: old total was double-counted)."""
        import paddle_tpu.nn as nn
        paddle.seed(0)
        lin = nn.Linear(1, 1, bias_attr=False)
        ma = paddle.incubate.ModelAverage(1.0, parameters=lin.parameters(),
                                          min_average_window=3,
                                          max_average_window=3)
        seen = []
        for v in [1.0, 2.0, 3.0, 4.0, 5.0]:
            lin.weight._data = np.full((1, 1), v, np.float32) * 0 + v
            ma.step()
            seen.append(v)
        # window restarted after the 3rd step; average covers the last
        # old-window (1,2,3) plus the live window (4,5) single-counted
        with ma.apply():
            avg = float(np.asarray(lin.weight._data).ravel()[0])
        np.testing.assert_allclose(avg, np.mean(seen), rtol=1e-5)

    def test_lookahead_inherited_entry_points(self):
        import paddle_tpu.nn as nn
        paddle.seed(0)
        lin = nn.Linear(2, 2)
        sgd = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        la = paddle.incubate.LookAhead(sgd, alpha=0.5, k=2)
        la.set_lr(0.05)
        assert la.get_lr() == pytest.approx(0.05)
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        loss = (lin(x) ** 2).mean()
        loss.backward()
        la.minimize_step()  # the class alias must dispatch to LookAhead.step
        assert la._k_count == 1

    def test_auto_checkpoint_resume(self, tmp_path):
        from paddle_tpu.incubate.checkpoint import train_epoch_range
        state = {"v": 0}
        saved = {}

        def save_fn(path):
            saved["v"] = state["v"]
            with open(path, "w") as f:
                f.write(str(state["v"]))

        def load_fn(path):
            state["v"] = int(open(path).read())

        ran = []
        for e in train_epoch_range(3, save_fn=save_fn, load_fn=load_fn,
                                   checkpoint_dir=str(tmp_path),
                                   save_checkpoint_inter=0):
            state["v"] = e
            ran.append(e)
        assert ran == [0, 1, 2]
        ran2 = []
        for e in train_epoch_range(5, save_fn=save_fn, load_fn=load_fn,
                                   checkpoint_dir=str(tmp_path),
                                   save_checkpoint_inter=0):
            ran2.append(e)
        assert ran2 == [3, 4]  # resumed past completed epochs
        assert state["v"] == 2  # restored from snapshot


class TestL1Decay:
    def test_l1_is_sign_gradient(self):
        import paddle_tpu.nn as nn
        paddle.seed(0)
        lin = nn.Linear(2, 2, bias_attr=False)
        w0 = np.array(lin.weight._data)
        opt = paddle.optimizer.SGD(
            learning_rate=0.1, parameters=lin.parameters(),
            weight_decay=paddle.regularizer.L1Decay(0.5))
        # zero data gradient: update must be pure L1 shrink = lr*coeff*sign(w)
        lin.weight._grad = np.zeros_like(w0)
        import jax.numpy as jnp
        lin.weight._grad = jnp.zeros_like(lin.weight._data)
        opt.step()
        np.testing.assert_allclose(np.array(lin.weight._data),
                                   w0 - 0.1 * 0.5 * np.sign(w0), rtol=1e-5)


class TestSparseNativeOps:
    def test_sparse_add_stays_sparse(self):
        import jax
        ind1 = np.array([[0, 1], [0, 1]])
        ind2 = np.array([[0, 2], [0, 2]])
        a = paddle.sparse.sparse_coo_tensor(ind1, np.array([1., 2.], dtype=np.float32), [3, 3])
        b = paddle.sparse.sparse_coo_tensor(ind2, np.array([10., 20.], dtype=np.float32), [3, 3])
        out = paddle.sparse.add(a, b)
        assert paddle.sparse.is_sparse(out)
        ref = np.asarray(a.to_dense()._data) + np.asarray(b.to_dense()._data)
        np.testing.assert_array_equal(np.asarray(out.to_dense()._data), ref)
        # jit-safe: static nse bound
        f = jax.jit(lambda: paddle.sparse.add(a, b).to_dense()._data)
        np.testing.assert_array_equal(np.asarray(f()), ref)

    def test_executor_feed_by_name(self):
        exe = paddle.static.Executor()
        out = exe.run(lambda x, y: x - y,
                      feed={"y": np.ones(2, np.float32),
                            "x": np.full(2, 3.0, np.float32)})
        np.testing.assert_array_equal(out[0], np.full(2, 2.0, np.float32))

    def test_conll_mode_split(self, tmp_path):
        f = tmp_path / "words.txt"
        blocks = []
        for i in range(10):
            blocks.append(f"word{i}\nother{i}\n")
        f.write_text("\n".join(blocks) + "\n")
        tr = paddle.text.Conll05st(data_file=str(tmp_path), mode="train")
        te = paddle.text.Conll05st(data_file=str(tmp_path), mode="test")
        assert len(tr) == 8 and len(te) == 2


class TestTopLevelParity:
    def test_new_namespace_modules(self, tmp_path):
        assert paddle.compat.to_text(b"abc") == "abc"
        assert paddle.compat.to_bytes("abc") == b"abc"
        assert os.path.isdir(paddle.sysconfig.get_include())
        # hub over a local hubconf
        (tmp_path / "hubconf.py").write_text(
            "def tiny(width=4):\n"
            "    'a tiny model'\n"
            "    import paddle_tpu.nn as nn\n"
            "    return nn.Linear(width, 2)\n")
        assert paddle.hub.list(str(tmp_path)) == ["tiny"]
        assert "tiny model" in paddle.hub.help(str(tmp_path), "tiny")
        layer = paddle.hub.load(str(tmp_path), "tiny", width=3)
        assert layer.weight.shape[0] == 3
        with pytest.raises(ValueError, match="zero-egress"):
            paddle.hub.list("whatever", source="github")

    def test_batch_and_reader_decorators(self):
        r = lambda: iter(range(10))
        batches = list(paddle.batch(r, 3)())
        assert batches[0] == [0, 1, 2] and len(batches) == 4
        batches = list(paddle.batch(r, 3, drop_last=True)())
        assert len(batches) == 3
        buf = list(paddle.reader.buffered(r, 2)())
        assert buf == list(range(10))
        comp = list(paddle.reader.chain(r, r)())
        assert len(comp) == 20
        mapped = list(paddle.reader.xmap_readers(lambda x: x * 2, r, 2, 4,
                                                 order=True)())
        assert mapped == [2 * i for i in range(10)]

    def test_places_and_legacy_aliases(self):
        assert paddle.CPUPlace().is_cpu_place()
        with pytest.raises(RuntimeError):
            paddle.CUDAPlace(0)
        assert paddle.Model is not None
        assert paddle.ParamAttr is not None
        assert paddle.VarBase is paddle.Tensor
        assert paddle.in_dygraph_mode() is True
        assert paddle.get_cuda_rng_state() == []
        paddle.disable_signal_handler()
        t = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        assert paddle.tolist(t) == [1.0, 2.0]
        assert tuple(paddle.t(paddle.to_tensor(
            np.zeros((2, 3), np.float32))).shape) == (3, 2)
        assert float(np.asarray(paddle.add_n(
            [t, t])._data)[0]) == 2.0


class TestInplaceOpsAutograd:
    def test_inplace_ops_keep_gradients(self):
        """round-2 review: *_ ops must _adopt so the tape's out_refs follow
        the mutated tensor (direct _data/_node assignment orphaned them)."""
        import paddle_tpu.tensor as T
        w = paddle.to_tensor(np.array([[2.0, 3.0]], np.float32),
                             stop_gradient=False)
        y = w * 4.0                       # recorded node
        T.squeeze_(y)                     # in-place on a non-leaf
        y.sum().backward()
        np.testing.assert_allclose(np.asarray(w.grad._data), [[4.0, 4.0]])

        x = paddle.to_tensor(np.array([0.5], np.float32), stop_gradient=False)
        z = x * 2.0
        T.tanh_(z)
        z.backward()
        np.testing.assert_allclose(np.asarray(x.grad._data),
                                   2.0 * (1 - np.tanh(1.0) ** 2), rtol=1e-6)

    def test_sci_mode_forces_scientific(self):
        import paddle_tpu.tensor as T
        T.set_printoptions(sci_mode=True, precision=2)
        try:
            s = repr(np.array([1.5, 20.0]))
            assert "e+" in s or "e-" in s, s
        finally:
            np.set_printoptions(suppress=False, formatter=None, precision=8)


class TestNamespaceParityTail:
    def test_vision_top_level_exports(self):
        import paddle_tpu.vision as v
        for n in ("MNIST", "Cifar10", "Compose", "Normalize", "Flowers",
                  "DatasetFolder", "ImageFolder", "resnet50"):
            assert hasattr(v, n), n
        assert v.get_image_backend() == "numpy"

    def test_dataset_folder(self, tmp_path):
        import paddle_tpu.vision as v
        for cls_name, val in [("cat", 1.0), ("dog", 2.0)]:
            d = tmp_path / cls_name
            d.mkdir()
            for i in range(3):
                np.save(d / f"s{i}.npy", np.full((4, 4), val, np.float32))
        ds = v.DatasetFolder(str(tmp_path))
        assert len(ds) == 6 and ds.classes == ["cat", "dog"]
        sample, target = ds[0]
        assert target == 0 and sample[0, 0] == 1.0
        flat = v.ImageFolder(str(tmp_path))
        assert len(flat) == 6

    def test_jit_legacy_surface(self):
        import paddle_tpu.jit as jit
        import paddle_tpu.nn as nn
        jit.set_verbosity(3)
        jit.set_code_level(5)
        calls = {"eager": 0}

        @jit.declarative
        def f(x):
            calls["eager"] += 1
            return x * 2

        t = paddle.to_tensor(np.ones(2, np.float32))
        f(t)
        jit.ProgramTranslator.get_instance().enable(False)
        try:
            f(t)  # runs the original callable eagerly
        finally:
            jit.ProgramTranslator.get_instance().enable(True)
        assert calls["eager"] >= 2  # traced once + eager once

        lin = nn.Linear(2, 2)
        out, traced = jit.TracedLayer.trace(lin, [t.reshape([1, 2])])
        out2 = traced(t.reshape([1, 2]))
        np.testing.assert_allclose(np.asarray(out._data),
                                   np.asarray(out2._data), rtol=1e-6)

    def test_distributed_namespace_tail(self):
        import paddle_tpu.distributed as dist
        assert dist.InMemoryDataset is paddle.io.InMemoryDataset
        e = dist.ProbabilityEntry(0.5)
        assert "0.5" in e._to_attr()
        with pytest.raises(ValueError):
            dist.ProbabilityEntry(1.5)
        with pytest.raises(RuntimeError, match="BoxPS"):
            dist.BoxPSDataset()
        eps, rank = dist.cloud_utils.get_cluster_and_pod()
        assert isinstance(eps, list) and rank >= 0
        assert callable(dist.shard_tensor) and callable(dist.gloo_barrier)
