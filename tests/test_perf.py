"""Tests for the ``repro.perf`` subsystem: AOT step export/load,
persistent compile cache, the profiling trace harness, and the
benchmark compare gate.

The load-bearing contracts:

  1. an AOT-loaded executable produces BITWISE the state the freshly
     compiled one does (an artifact dir is a cache, never a fork);
  2. a second session against a warm AOT dir reports ZERO compilations
     (the cold-start elimination is real, not probabilistic);
  3. the AOT key is value-independent for python scalars (the train
     step's ring slot varies per dispatch and must not fork artifacts)
     but forks on config/shape changes;
  4. enabling the persistent cache mid-process takes effect (jax
     initializes its cache object once - see cache._reset_cache_state).
"""
import glob
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import perf
from repro.perf import aot


def _leaves_bytes(tree):
    return [np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(tree)]


class TestAotKey:
    def test_python_scalars_are_value_independent(self):
        a = aot.step_key({"f": 1}, (jnp.ones(4), 3, 2.5, True))
        b = aot.step_key({"f": 1}, (jnp.ones(4), 9, 0.1, False))
        assert a == b

    def test_forks_on_facts_shapes_dtypes(self):
        base = aot.step_key({"f": 1}, (jnp.ones(4),))
        assert aot.step_key({"f": 2}, (jnp.ones(4),)) != base
        assert aot.step_key({"f": 1}, (jnp.ones(5),)) != base
        assert aot.step_key({"f": 1},
                            (jnp.ones(4, jnp.int32),)) != base

    def test_dataclass_facts_canonicalize(self):
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class Cfg:
            k: int = 6
        assert aot.digest(Cfg()) == aot.digest(Cfg())
        assert aot.digest(Cfg(k=7)) != aot.digest(Cfg())


class TestAotRoundtrip:
    def test_export_load_bit_identity(self, tmp_path):
        jitted = jax.jit(lambda s, x: (s * 1.5 + x, (s * x).sum()))
        args = (jnp.arange(8.0), jnp.full(8, 2.0))
        facts = {"prog": "t"}
        stats = {}
        cold = aot.load_or_compile(jitted, args, aot_dir=str(tmp_path),
                                   facts=facts, stats=stats)
        ref = jitted(*args)
        assert stats == {"compilations": 1, "aot_saves": 1}
        assert glob.glob(str(tmp_path / ("*" + aot.SUFFIX)))
        warm = aot.load_or_compile(jitted, args, aot_dir=str(tmp_path),
                                   facts=facts, stats=stats)
        assert stats["aot_loads"] == 1 and stats["compilations"] == 1
        for c, w, r in zip(_leaves_bytes(cold(*args)),
                           _leaves_bytes(warm(*args)), _leaves_bytes(ref)):
            assert c == w == r

    def test_corrupt_artifact_is_a_miss(self, tmp_path):
        jitted = jax.jit(lambda x: x + 1)
        args = (jnp.ones(4),)
        aot.load_or_compile(jitted, args, aot_dir=str(tmp_path),
                            facts="f", stats=None)
        [path] = glob.glob(str(tmp_path / ("*" + aot.SUFFIX)))
        with open(path, "wb") as f:
            f.write(b"torn")
        stats = {}
        fn = aot.load_or_compile(jitted, args, aot_dir=str(tmp_path),
                                 facts="f", stats=stats)
        assert stats == {"compilations": 1, "aot_saves": 1}
        np.testing.assert_array_equal(np.asarray(fn(*args)),
                                      np.asarray(jitted(*args)))

    def test_artifact_of_another_format_is_a_miss(self, tmp_path):
        """An artifact written under another FORMAT (e.g. before the
        packed wire layout changed) recompiles instead of loading."""
        import pickle
        jitted = jax.jit(lambda x: x + 1)
        args = (jnp.ones(4),)
        aot.load_or_compile(jitted, args, aot_dir=str(tmp_path),
                            facts="f", stats=None)
        [path] = glob.glob(str(tmp_path / ("*" + aot.SUFFIX)))
        with open(path, "rb") as f:
            art = pickle.load(f)
        art["format"] = aot.FORMAT - 1
        with open(path, "wb") as f:
            pickle.dump(art, f)
        stats = {}
        aot.load_or_compile(jitted, args, aot_dir=str(tmp_path),
                            facts="f", stats=stats)
        assert stats == {"compilations": 1, "aot_saves": 1}

    def test_key_names_the_wire_layout(self, monkeypatch):
        from repro.comm import bits
        args = (jnp.ones(4),)
        key = aot.step_key("f", args)
        monkeypatch.setattr(bits, "LAYOUT", bits.LAYOUT + 1)
        assert aot.step_key("f", args) != key

    def test_no_dir_passthrough(self):
        jitted = jax.jit(lambda x: x * 2)
        stats = {}
        fn = aot.load_or_compile(jitted, (jnp.ones(2),), aot_dir=None,
                                 facts="f", stats=stats)
        assert fn is jitted and stats == {"compilations": 1}


def _train_session(aot_dir, steps=2):
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.launch.mesh import make_local_mesh
    from repro.dist.step import make_train_step, TrainConfig
    from repro.train.session import SessionConfig, TrainSession
    from repro.data.pipeline import batch_for_model

    cfg = get_config("yi-6b", smoke=True)
    model = Model(cfg)
    mesh = make_local_mesh(data=1, model=1)
    tc = TrainConfig(grad_k=6, weight_k=None, worker_axes=())
    art = make_train_step(model, mesh, tc)
    sess = TrainSession.from_artifacts(
        art, batch_for_model(cfg, 32, 2, seed=0),
        SessionConfig(log_every=0, prefetch=0, aot_dir=aot_dir),
        log=lambda *_: None)
    sess.run(steps)
    state = jax.device_get(sess._state)
    stats = dict(sess.stats)
    sess.close()
    return state, stats


@pytest.mark.slow
class TestSessionAot:
    def test_second_train_session_zero_compilations(self, tmp_path):
        d = str(tmp_path / "aot")
        cold_state, cold = _train_session(d)
        warm_state, warm = _train_session(d)
        assert cold["compilations"] == 1 and cold["aot_saves"] == 1
        assert warm["compilations"] == 0 and warm["aot_loads"] == 1
        for a, b in zip(_leaves_bytes(cold_state),
                        _leaves_bytes(warm_state)):
            assert a == b

    def test_second_serve_session_zero_compilations(self, tmp_path):
        from repro.configs import get_config
        from repro.models.model import Model
        from repro.serve import Request, ServeSession

        cfg = get_config("yi-6b", smoke=True)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        d = str(tmp_path / "aot")

        def run():
            s = ServeSession(model, params, slots=2, max_seq=64, seed=0,
                             aot_dir=d)
            s.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
            res = s.drain()
            return list(res.values())[0].tokens, dict(s.stats)

        toks_c, cold = run()
        toks_w, warm = run()
        assert cold["compilations"] >= 1
        assert warm["compilations"] == 0 and warm["aot_loads"] >= 1
        assert toks_c == toks_w


class TestPersistentCache:
    @pytest.fixture
    def restore_cache_config(self):
        keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache")
        prev = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in prev.items():
            jax.config.update(k, v)
        perf.cache._reset_cache_state()

    def test_enable_after_first_compile_takes_effect(
            self, tmp_path, monkeypatch, restore_cache_config):
        d = str(tmp_path / "xla")
        monkeypatch.delenv(perf.cache.ENV_VAR, raising=False)
        monkeypatch.setattr(perf.cache, "DEFAULT_CACHE_DIR", d)
        # a compile BEFORE enabling initializes jax's cache state
        jax.jit(lambda x: x - 3)(jnp.ones(4)).block_until_ready()
        assert perf.enable_persistent_cache() == d
        jax.jit(lambda x: x * 3 + 7)(jnp.ones(16)).block_until_ready()
        assert perf.cache_entries(d) >= 1

    def test_env_set_leaves_the_directory_to_jax(
            self, tmp_path, monkeypatch, restore_cache_config):
        d = str(tmp_path / "from_env")
        monkeypatch.setenv(perf.cache.ENV_VAR, d)
        jax.config.update("jax_compilation_cache_dir", None)
        assert perf.enable_persistent_cache() == d
        # the code sets no directory: jax reads the variable itself
        assert jax.config.jax_compilation_cache_dir is None

    def test_env_unset_uses_the_fixed_checkout_path(
            self, monkeypatch, restore_cache_config):
        monkeypatch.delenv(perf.cache.ENV_VAR, raising=False)
        d = perf.enable_persistent_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert d == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
        assert os.path.isdir(d)

    def test_cache_entries_ignores_sidecars(self, tmp_path):
        (tmp_path / "entry").write_bytes(b"x")
        (tmp_path / "entry-atime").write_bytes(b"x")
        (tmp_path / ".hidden").write_bytes(b"x")
        assert perf.cache_entries(str(tmp_path)) == 1


class TestTraceHarness:
    def test_trace_writes_profile(self, tmp_path):
        d = str(tmp_path / "tr")
        with perf.trace(d) as out:
            assert out == d
            with perf.span("bench:test"):
                jax.jit(lambda x: x @ x)(jnp.ones((32, 32))
                                         ).block_until_ready()
        runs = perf.profiling.trace_runs(d)
        assert len(runs) == 1
        assert glob.glob(os.path.join(runs[0], "*.xplane.pb"))

    def test_trace_disabled_is_noop(self, tmp_path):
        d = str(tmp_path / "tr")
        with perf.trace(d, enabled=False) as out:
            assert out is None
        assert not os.path.exists(d)


class TestAutotune:
    def test_tune_restores_when_not_installed(self):
        from repro.comm import kernels as K
        res = perf.autotune.tune_enc_rows(candidates=(8, 16), iters=1,
                                          numel=1 << 12, install=False)
        assert res["best"] in (8, 16)
        assert set(res["timings_s"]) == {8, 16}
        assert K.enc_rows() == K.ENC_ROWS   # override not left behind

    def test_tune_mm_cols_restores_when_not_installed(self):
        from repro.comm import matmul as MM
        res = perf.autotune.tune_mm_cols(candidates=(128, 256), iters=1,
                                         m=4, k=256, n=256, install=False)
        # 256 % 128 == 0 and 256 % 256 == 0: both candidates measured
        assert res["best"] in (128, 256)
        assert set(res["timings_s"]) == {128, 256}
        assert MM.mm_cols() == MM.MM_COLS   # override not left behind

    def test_tune_mm_cols_skips_non_covering_tiles(self):
        res = perf.autotune.tune_mm_cols(candidates=(128, 512), iters=1,
                                         m=4, k=256, n=256, install=False)
        assert set(res["timings_s"]) == {128}  # 512 can't tile n=256


def _compare_mod():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:  # benchmarks/ is a namespace package
        sys.path.insert(0, root)
    return importlib.import_module("benchmarks.compare")


class TestCompareGate:
    def test_ratio_floor_catches_the_pr5_regression(self):
        compare = _compare_mod()
        base = [{"name": "comm_decode_speedup_log_6", "us_per_call": 0.0,
                 "derived": "1.03x"}]
        bad = [{"name": "comm_decode_speedup_log_6", "us_per_call": 0.0,
                "derived": "0.23x", "ratio": 0.23}]
        good = [{"name": "comm_decode_speedup_log_6", "us_per_call": 0.0,
                 "derived": "1.46x", "ratio": 1.46}]
        [fail] = compare.compare(base, bad)
        assert fail["status"] == "FAIL" and "floor" in fail["detail"]
        [ok] = compare.compare(base, good)
        assert ok["status"] == "ok"

    def test_legacy_baseline_derived_ratio_parses(self):
        compare = _compare_mod()
        assert compare.row_ratio({"derived": "0.23x"}) == 0.23
        assert compare.row_ratio({"derived": "4.43GB_s_4MB"}) is None

    def test_time_budget_gate(self):
        compare = _compare_mod()
        base = [{"name": "comm_encode_fused_log_6", "us_per_call": 100.0,
                 "derived": ""}]
        new = [{"name": "comm_encode_fused_log_6", "us_per_call": 300.0,
                "derived": ""}]
        [off] = compare.compare(base, new)
        assert off["status"] == "ok"          # machine-dependent: opt-in
        [on] = compare.compare(base, new, gate_times=True, time_budget=2.0)
        assert on["status"] == "FAIL"
