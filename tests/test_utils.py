"""Recorder + checkpoint unit tests (reference: lib/recorder.py,
helper_funcs weight save/load)."""

import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.utils import (
    Recorder,
    latest_checkpoint,
    load_checkpoint,
    save_checkpoint,
)


class TestRecorder:
    def test_segments(self):
        rec = Recorder(verbose=False)
        rec.start_epoch()
        rec.start()
        time.sleep(0.01)
        rec.end("calc")
        rec.start()
        rec.end("wait")
        assert rec.epoch_segments["calc"] >= 0.01
        assert rec.epoch_segments["comm"] == 0.0

    def test_train_window_and_save_load(self, tmp_path):
        rec = Recorder(verbose=False)
        for i in range(10):
            rec.train_error(i, loss=1.0 / (i + 1), err=0.5)
        rec.val_error(0.3, 0.1, 0.01)
        rec.save(tmp_path / "rec.json")
        rec2 = Recorder(verbose=False)
        rec2.load(tmp_path / "rec.json")
        assert rec2.n_iter == 10
        assert rec2.train_losses == rec.train_losses
        assert rec2.val_records == [{"loss": 0.3, "err": 0.1, "err_top5": 0.01}]

    def test_bad_mode_asserts(self):
        rec = Recorder(verbose=False)
        rec.start()
        with pytest.raises(AssertionError):
            rec.end("compute")


class TestCheckpoint:
    def _trees(self):
        return {
            "params": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)},
            "opt_state": {"m": {"w": jnp.zeros((2, 3)), "b": jnp.zeros(3)}},
        }

    def test_roundtrip(self, tmp_path):
        trees = self._trees()
        save_checkpoint(tmp_path, 5, trees, meta={"epoch": 5, "lr": 0.01})
        path = latest_checkpoint(tmp_path)
        assert path is not None and path.name == "ckpt_5.npz"
        loaded, meta = load_checkpoint(path, trees)
        assert meta == {"epoch": 5, "lr": 0.01}
        np.testing.assert_array_equal(
            np.asarray(loaded["params"]["w"]), np.arange(6.0).reshape(2, 3)
        )

    def test_latest_picks_highest_step(self, tmp_path):
        trees = self._trees()
        for step in (1, 10, 2):
            save_checkpoint(tmp_path, step, trees)
        assert latest_checkpoint(tmp_path).name == "ckpt_10.npz"

    def test_shape_mismatch_raises(self, tmp_path):
        trees = self._trees()
        save_checkpoint(tmp_path, 0, trees)
        bad = {"params": {"w": jnp.zeros((4, 4)), "b": jnp.ones(3)},
               "opt_state": trees["opt_state"]}
        with pytest.raises(ValueError, match="shape"):
            load_checkpoint(latest_checkpoint(tmp_path), bad)

    def test_missing_leaf_raises(self, tmp_path):
        trees = self._trees()
        save_checkpoint(tmp_path, 0, trees)
        bigger = {
            "params": {**trees["params"], "extra": jnp.zeros(2)},
            "opt_state": trees["opt_state"],
        }
        with pytest.raises(KeyError):
            load_checkpoint(latest_checkpoint(tmp_path), bigger)

    def test_empty_dir_returns_none(self, tmp_path):
        assert latest_checkpoint(tmp_path / "nope") is None


class TestResampleLabels:
    """Label-noise helper shared by the synthetic and real-CIFAR data
    paths (the noise floor of ``tests/test_convergence.py``'s
    drills)."""

    def test_deterministic_and_fraction(self):
        from theanompi_tpu.models.data.synthetic import resample_labels

        y = np.random.default_rng(1).integers(0, 10, 4000).astype(np.int32)
        y0 = y.copy()
        a = resample_labels(y, 0.25, 10, seed=0, salt=3)
        b = resample_labels(y, 0.25, 10, seed=0, salt=3)
        np.testing.assert_array_equal(a, b)      # same seed+salt
        assert (resample_labels(y, 0.25, 10, seed=0, salt=4) != a).any()
        np.testing.assert_array_equal(y, y0)     # input untouched
        # effective flip rate ~ frac * (C-1)/C = 0.225
        frac = float((a != y).mean())
        assert 0.18 < frac < 0.27, frac

    def test_zero_noise_identity(self):
        from theanompi_tpu.models.data.synthetic import resample_labels

        y = np.arange(100, dtype=np.int32) % 10
        np.testing.assert_array_equal(
            resample_labels(y, 0.0, 10, seed=0, salt=3), y
        )


class TestCompileCacheRule:
    """``utils.enable_compile_cache``: whoever runs the program places
    the cache (``JAX_COMPILATION_CACHE_DIR``); otherwise it is the one
    fixed path under the checkout.  Each case is a fresh interpreter:
    JAX reads the variable when it is imported."""

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _dirs(self, env_value):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_value is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_value
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax\n"
             "from theanompi_tpu.utils import enable_compile_cache\n"
             "print(enable_compile_cache())\n"
             "print(jax.config.jax_compilation_cache_dir)\n"],
            cwd=self.ROOT, env=env, capture_output=True, text=True,
            timeout=120, check=True,
        ).stdout.split()
        return out

    def test_variable_set_is_left_alone(self, tmp_path):
        where = str(tmp_path / "placed_from_outside")
        assert self._dirs(where) == [where, where]

    def test_unset_is_the_fixed_checkout_path(self):
        # never a temporary, pid- or time-derived path: the path is
        # part of the cache key, and two runs must agree on it
        fixed = os.path.join(self.ROOT, ".jax_cache")
        assert self._dirs(None) == [fixed, fixed]
        assert self._dirs(None) == [fixed, fixed]
