"""TrainSession: zero per-step host syncs, bit-identical resume for the
dist and single-machine paths, crash-safe versioned checkpoints, and the
eval-history fix."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.configs import get_config
from repro.core.qadam import QAdamConfig, qadam
from repro.data import pipeline as dp
from repro.dist.step import TrainConfig, make_train_step
from repro.launch.mesh import make_local_mesh
from repro.models.model import Model
from repro.train.session import SessionConfig, TrainSession


SEQ, BATCH = 16, 2


@pytest.fixture(scope="module")
def yi():
    cfg = get_config("yi-6b", smoke=True)
    return cfg, Model(cfg)


@pytest.fixture(scope="module")
def qadam_art(yi):
    cfg, model = yi
    mesh = make_local_mesh(data=1, model=1)
    tc = TrainConfig(alpha=1e-2, grad_k=4, weight_k=7,
                     weight_absolute=True, worker_axes=())
    return make_train_step(model, mesh, tc)


def _batches(cfg, seed=0):
    return dp.batch_for_model(cfg, SEQ, BATCH, seed=seed)


def _masters(state):
    return jax.tree.map(np.asarray, state["master"])


def _max_err(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(np.max(np.abs(x - y))), a, b)))


quiet = lambda *_: None


class TestHotLoop:
    def test_steady_state_zero_host_syncs(self, yi, qadam_art, monkeypatch):
        """With logging off, N training steps are N dispatches and ZERO
        device->host transfers - losses stay in the device ring buffer
        until explicitly harvested (mirrors test_serve_session)."""
        cfg, _ = yi
        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg), SessionConfig(log_every=0),
            log=quiet)
        sess.run(1)  # compile + warm the prefetcher outside the counter

        gets = {"n": 0}
        real_get = jax.device_get

        def counting_get(x):
            gets["n"] += 1
            return real_get(x)

        monkeypatch.setattr(jax, "device_get", counting_get)
        d0 = sess.stats["dispatches"]
        sess.run(8)
        assert gets["n"] == 0
        assert sess.stats["dispatches"] - d0 == 8
        assert sess.stats["syncs"] == 0
        # one explicit harvest = ONE sync for every resident loss
        out = sess.harvest_losses()
        assert gets["n"] == 1 and sess.stats["syncs"] == 1
        assert [s for s, _ in out][-1] == 9
        assert all(np.isfinite(v) for _, v in out)
        monkeypatch.undo()
        sess.close()

    def test_log_cadence_harvests_per_boundary(self, yi, qadam_art):
        cfg, _ = yi
        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg), SessionConfig(log_every=4), log=quiet)
        hist = sess.run(8)
        sess.close()
        assert [h["step"] for h in hist] == [1, 4, 8]
        # syncs scale with log boundaries, not steps
        assert sess.stats["syncs"] == 3 and sess.stats["steps"] == 8

    def test_scan_chunk_matches_per_step(self, yi, qadam_art):
        """Chunked dispatch (lax.scan over stacked batches) reproduces the
        per-step path's history."""
        cfg, _ = yi
        a = TrainSession.from_artifacts(
            qadam_art, _batches(cfg), SessionConfig(log_every=4), log=quiet)
        ha = a.run(8)
        a.close()
        b = TrainSession.from_artifacts(
            qadam_art, _batches(cfg),
            SessionConfig(log_every=4, scan_chunk=4), log=quiet)
        hb = b.run(8)
        b.close()
        la = {h["step"]: h["loss"] for h in ha}
        lb = {h["step"]: h["loss"] for h in hb}
        for s in (4, 8):
            np.testing.assert_allclose(la[s], lb[s], rtol=2e-4)
        assert b.stats["dispatches"] == 2

    def test_tail_chunk_and_repeated_runs(self, yi, qadam_art):
        cfg, _ = yi
        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg),
            SessionConfig(log_every=0, scan_chunk=4), log=quiet)
        sess.run(6)    # 4 + tail 2
        sess.run(5)    # 4 + tail 1 (still a stacked scan dispatch)
        sess.close()
        assert sess.step == 11
        assert sess.stats["dispatches"] == 4

    def test_eval_gets_own_history_entry(self, yi, qadam_art):
        """The old loop pinned evals onto the latest *log* entry; evals
        now land at their own step even when cadences are coprime."""
        cfg, _ = yi
        evals = []

        def eval_fn(state):
            evals.append(int(np.asarray(state["count"])))
            return {"acc": evals[-1]}

        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg),
            SessionConfig(log_every=2, eval_every=3, eval_fn=eval_fn),
            log=quiet)
        hist = sess.run(6)
        sess.close()
        ev = [(h["step"], h["eval"]["acc"]) for h in hist if "eval" in h]
        assert ev == [(3, 3), (6, 6)]
        assert all("loss" not in h for h in hist if "eval" in h)

    def test_divergence_raises_at_harvest(self, yi):
        cfg, model = yi
        mesh = make_local_mesh(data=1, model=1)
        # absurd LR to force a non-finite loss quickly
        tc = TrainConfig(alpha=1e6, grad_k=None, weight_k=None,
                         worker_axes=())
        art = make_train_step(model, mesh, tc)
        sess = TrainSession.from_artifacts(
            art, _batches(cfg), SessionConfig(log_every=2), log=quiet)
        with pytest.raises(FloatingPointError):
            sess.run(20)
        sess.close()


class TestResume:
    def _dist_resume_case(self, yi, tc, tmp_path, chunk=1):
        cfg, model = yi
        mesh = make_local_mesh(data=1, model=1)
        art = make_train_step(model, mesh, tc)
        sc = lambda **kw: SessionConfig(log_every=0, scan_chunk=chunk, **kw)

        full = TrainSession.from_artifacts(art, _batches(cfg), sc(),
                                           log=quiet)
        full.run(6)
        full.close()
        want = _masters(full.state)

        d = str(tmp_path)
        first = TrainSession.from_artifacts(
            art, _batches(cfg), sc(ckpt_dir=d, ckpt_every=2), log=quiet)
        first.run(2)
        first.close()   # flushes the async writer
        assert store.latest_step(d) == 2

        second = TrainSession.from_artifacts(
            art, _batches(cfg), sc(ckpt_dir=d), log=quiet)
        assert second.resume() == 2
        second.run(4)
        second.close()
        assert _max_err(want, _masters(second.state)) == 0.0

    def test_dist_qadam_bit_identical(self, yi, tmp_path):
        """Train 6 uninterrupted vs 2 + checkpoint + restore + 4: final
        master weights agree BIT-FOR-BIT (quantized wire, EF, Q_x on)."""
        self._dist_resume_case(yi, TrainConfig(
            alpha=1e-2, grad_k=4, weight_k=7, weight_absolute=True,
            worker_axes=()), tmp_path)

    def test_dist_dp_adam_bit_identical(self, yi, tmp_path):
        self._dist_resume_case(yi, TrainConfig(
            alpha=1e-2, mode="dp_adam", grad_k=None, weight_k=None,
            worker_axes=()), tmp_path, chunk=2)

    def test_single_machine_bit_identical(self, yi, tmp_path):
        """Same contract for the single-machine Algorithm-1 path
        (QAdamState incl. its PRNG key round-trips the store)."""
        cfg, model = yi
        params = model.init(jax.random.PRNGKey(0))
        opt = qadam(QAdamConfig(alpha=1e-2, grad_q="log:4",
                                weight_q="uniform:7",
                                weight_q_min_numel=2 ** 14))

        def lfn(p, batch):
            ls, nt = model.loss(p, batch)
            return ls / nt

        full = TrainSession.from_optimizer(
            opt, lfn, params, _batches(cfg), SessionConfig(log_every=0),
            log=quiet)
        full.run(6)
        full.close()
        want = jax.tree.map(np.asarray, full.state["params"])

        d = str(tmp_path)
        first = TrainSession.from_optimizer(
            opt, lfn, params, _batches(cfg),
            SessionConfig(log_every=0, ckpt_dir=d, ckpt_async=False),
            log=quiet)
        first.run(3)
        first.checkpoint()
        first.close()

        second = TrainSession.from_optimizer(
            opt, lfn, params, _batches(cfg),
            SessionConfig(log_every=0, ckpt_dir=d), log=quiet)
        assert second.resume() == 3
        second.run(3)
        second.close()
        got = jax.tree.map(np.asarray, second.state["params"])
        assert _max_err(want, got) == 0.0

    def test_resume_without_checkpoint_is_noop(self, yi, qadam_art,
                                               tmp_path):
        cfg, _ = yi
        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg),
            SessionConfig(ckpt_dir=str(tmp_path)), log=quiet)
        assert sess.resume() == 0
        sess.close()


class TestCheckpointStore:
    def test_versioned_subdirs_and_pruning(self, tmp_path):
        tree = {"w": jnp.arange(8, dtype=jnp.float32)}
        for s in (2, 4, 6, 8):
            store.save(str(tmp_path), {"w": tree["w"] + s}, step=s, keep=2)
        names = sorted(os.listdir(tmp_path))
        assert names == ["step_00000006", "step_00000008"]
        assert store.latest_step(str(tmp_path)) == 8
        out = store.restore(str(tmp_path), tree, step=6)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(8, dtype=np.float32) + 6)

    def test_codec_leaves_record_the_wire_layout(self, tmp_path):
        """Codec-compressed leaves name the packed byte layout; a leaf
        packed in another layout is refused, never decoded wrongly."""
        import json
        from repro.comm.bits import LAYOUT
        tree = {"master": jnp.linspace(-1, 1, 1000, dtype=jnp.float32),
                "m": jnp.linspace(-2, 2, 1000, dtype=jnp.float32)}
        d = store.save(str(tmp_path), tree, step=1, codec="uniform_amax:5")
        out = store.restore(str(tmp_path), tree)
        np.testing.assert_array_equal(np.asarray(out["master"]),
                                      np.asarray(tree["master"]))
        np.testing.assert_allclose(np.asarray(out["m"]),
                                   np.asarray(tree["m"]), atol=2 / 32)
        path = os.path.join(d, "manifest.json")
        with open(path) as f:
            manifest = json.load(f)
        [ent] = [l for l in manifest["leaves"] if l.get("codec")]
        assert ent["layout"] == LAYOUT
        del ent["layout"]                      # as written before layouts
        with open(path, "w") as f:
            json.dump(manifest, f)
        with pytest.raises(ValueError, match="wire layout"):
            store.restore(str(tmp_path), tree)

    def test_crash_mid_save_keeps_previous(self, tmp_path, monkeypatch):
        """A crash while writing step 2 leaves step 1 intact and
        restorable - the manifest only becomes visible via the atomic
        rename after the payload is fully on disk."""
        tree = {"w": jnp.ones((4,), jnp.float32)}
        store.save(str(tmp_path), tree, step=1, extra={"batches_consumed": 1})

        def boom(*a, **k):
            raise OSError("disk full")

        monkeypatch.setattr(np, "savez", boom)
        with pytest.raises(OSError):
            store.save(str(tmp_path), tree, step=2)
        monkeypatch.undo()
        assert store.latest_step(str(tmp_path)) == 1
        assert not any(n.startswith("step_00000002")
                       for n in os.listdir(tmp_path))
        out = store.restore(str(tmp_path), tree)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(4))
        assert store.read_extra(str(tmp_path)) == {"batches_consumed": 1}

    def test_partial_dir_ignored_by_latest(self, tmp_path):
        tree = {"w": jnp.ones((2,), jnp.float32)}
        store.save(str(tmp_path), tree, step=3)
        os.makedirs(tmp_path / "step_00000009")   # no manifest: partial
        assert store.latest_step(str(tmp_path)) == 3
        out = store.restore(str(tmp_path), tree)  # resolves to step 3
        np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(2))

    def test_tail_misaligned_checkpoint_labels_true_step(
            self, yi, qadam_art, tmp_path):
        """run() tails can desync dispatches from the ckpt cadence; a
        boundary crossed mid-dispatch must label the checkpoint with the
        state's TRUE step (else resume() silently repeats steps)."""
        cfg, _ = yi
        d = str(tmp_path)
        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg),
            SessionConfig(log_every=0, scan_chunk=4, ckpt_every=4,
                          ckpt_dir=d, ckpt_keep=10), log=quiet)
        sess.run(6)   # dispatches 1-4 (ckpt @4), 5-6
        sess.run(6)   # dispatches 7-10 (boundary 8 crossed), 11-12 (@12)
        sess.close()
        steps = [int(n.split("_")[1]) for n in sorted(os.listdir(d))]
        assert steps == [4, 10, 12]
        for s in steps:
            tree = store.restore(d, sess.state, step=s)
            assert int(np.asarray(tree["count"])) == s
            assert store.read_extra(d, step=s)["batches_consumed"] == s

    def test_async_writer_flush(self, yi, qadam_art, tmp_path):
        cfg, _ = yi
        sess = TrainSession.from_artifacts(
            qadam_art, _batches(cfg),
            SessionConfig(log_every=0, ckpt_dir=str(tmp_path),
                          ckpt_every=2, ckpt_keep=1), log=quiet)
        sess.run(4)
        sess.wait_for_checkpoints()
        assert store.latest_step(str(tmp_path)) == 4
        assert sorted(os.listdir(tmp_path)) == ["step_00000004"]  # pruned
        sess.close()


class TestDataPipeline:
    def test_lm_batches_yield_host_numpy(self):
        cfg = dp.LMDataConfig(vocab_size=64, seq_len=16, global_batch=2)
        b = next(dp.lm_batches(cfg))
        assert all(isinstance(v, np.ndarray) for v in b.values())
        b2 = next(dp.batch_for_model(get_config("yi-6b", smoke=True),
                                     16, 2))
        assert all(isinstance(v, np.ndarray) for v in b2.values())

    def test_classification_batch_larger_than_dataset(self):
        x, y, *_ = dp.classification_dataset(dp.ClsDataConfig(
            n_train=16, n_test=4))
        with pytest.warns(UserWarning, match="replacement"):
            it = dp.classification_batches(x, y, 32)
            bx, by = next(it)
        assert bx.shape[0] == 32
        # small batches keep the no-replacement draw (and stay silent)
        bx2, _ = next(dp.classification_batches(x, y, 8))
        assert bx2.shape[0] == 8


class TestLoopShim:
    def test_train_shim_returns_state_history(self, yi, qadam_art):
        from repro.train.loop import LoopConfig, train
        cfg, _ = yi
        state, hist = train(qadam_art, qadam_art.config, _batches(cfg),
                            LoopConfig(steps=4, log_every=2), log=quiet)
        assert [h["step"] for h in hist] == [1, 2, 4]
        assert "master" in state
