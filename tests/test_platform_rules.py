"""The rules that keep the program from hiding its device or its kernel:
one compile-cache rule, no default peak for an unknown device, no guessed
HBM limit on an accelerator, no probe that turns a compiler error into
another kernel, and entry points that refuse to measure without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def as_accelerator(monkeypatch):
    """``on_accelerator()`` -> True while the live backend stays the CPU:
    the state a chip whose compiler refuses a kernel would be in."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setattr(H, "ACCEL_BACKENDS", ("cpu",))


# ---------------------------------------------------------- compile cache

def test_cache_rule_env_set_program_sets_no_directory(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set => JAX's own handling is the whole
    story: the program resolves that directory (and hangs the AOT
    store off it) but never sets one."""
    import jax

    from lightgbm_tpu.fleet.aot import aot_dir_from_env
    from lightgbm_tpu.utils import platform as PF
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    prev = jax.config.jax_compilation_cache_dir
    assert PF.enable_compile_cache(family="train") == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == prev
    assert aot_dir_from_env() == str(tmp_path / "serving")


def test_cache_rule_env_unset_fixed_path_across_processes():
    """Env unset => <checkout>/.jax_cache, the same in every process (the
    path is part of the cache key: no pid, time or temp name in it), on
    by default for a plain lgb.train."""
    code = (
        "import jax, numpy as np, lightgbm_tpu as lgb\n"
        "from lightgbm_tpu.utils.platform import compile_cache_dir\n"
        "X = np.random.RandomState(0).rand(200, 4); y = X[:, 0] > .5\n"
        "lgb.train({'objective': 'binary', 'verbosity': -1, "
        "'num_leaves': 4}, lgb.Dataset(X, label=y), 1, verbose_eval=False)\n"
        "print(compile_cache_dir())\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("LGBM_TPU_COMPILE_CACHE", None)
    outs = [subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=240)
            for _ in range(2)]
    for p in outs:
        assert p.returncode == 0, p.stderr[-2000:]
        assert p.stdout.split() == [os.path.join(REPO, ".jax_cache")] * 2
    assert outs[0].stdout == outs[1].stdout


def test_cache_entries_exclude_reserved_subtrees(tmp_path):
    from lightgbm_tpu.utils.platform import (compile_cache_entries,
                                             compile_cache_entries_by_family)
    assert compile_cache_entries(str(tmp_path / "missing")) == 0
    (tmp_path / "blob-a").write_text("x")
    (tmp_path / "serving").mkdir()
    (tmp_path / "serving" / "m-b8.bin").write_text("x")
    assert compile_cache_entries(str(tmp_path)) == 1
    assert compile_cache_entries_by_family(str(tmp_path)) == {
        "jit": 1, "serving_aot": 1}


def test_cpu_mesh_env_replaces_the_device_count():
    from lightgbm_tpu.utils.platform import cpu_mesh_env
    env = cpu_mesh_env(4, base={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8 --foo=1",
        "JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].split() == [
        "--foo=1", "--xla_force_host_platform_device_count=4"]


# ------------------------------------------------- device facts, no guesses

class _Dev:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_hbm_limit_is_not_guessed_on_an_accelerator(monkeypatch,
                                                    as_accelerator):
    """A chip that reports no bytes_limit is an error (the CPU allocator,
    standing in for one here, reports none); the env override still
    plans against a stated limit, and ``source`` says which."""
    from lightgbm_tpu.ops.planner import hbm_limit_bytes
    monkeypatch.delenv("LGBM_TPU_HBM_BYTES", raising=False)
    with pytest.raises(RuntimeError, match="bytes_limit"):
        hbm_limit_bytes()
    monkeypatch.setenv("LGBM_TPU_HBM_BYTES", str(8 << 30))
    assert hbm_limit_bytes() == (8 << 30, "env")


# --------------------------------------- probes check numbers, not compiles

def test_compile_error_in_the_fused_probe_propagates(as_accelerator,
                                                     monkeypatch):
    """Compiling the kernel for real on the CPU backend is refused ("Only
    interpret mode is supported") — the probe must let that out instead
    of answering False and electing the staged family."""
    from lightgbm_tpu.ops import fused as FU
    monkeypatch.setattr(FU, "_FUSED_PROBE", {})
    with pytest.raises(ValueError, match="interpret mode"):
        FU.fused_kernel_verified()
    assert FU._FUSED_PROBE == {}


def test_compile_error_in_the_predict_probe_propagates(monkeypatch):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops import predict_kernels as PK
    from lightgbm_tpu.predict import DeviceForest, StackedForest
    rng = np.random.RandomState(0)
    X = rng.rand(300, 4).astype(np.float32)
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 4, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=X[:, 0] > 0.5), 2,
                    verbose_eval=False)
    monkeypatch.setattr(H, "ACCEL_BACKENDS", ("cpu",))
    monkeypatch.setattr(PK, "_FUSED_PREDICT_PROBE", {})
    with pytest.raises(ValueError, match="interpret mode"):
        DeviceForest(StackedForest(bst.models), variant="fused",
                     chunk_rows=64, tile_rows=8)


def test_numeric_mismatch_still_demotes_with_a_warning(monkeypatch, capsys):
    """The probes keep their numeric role: a wrong histogram demotes
    auto to the staged family, at warning level through utils/log."""
    from lightgbm_tpu.ops import fused as FU
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.utils import log
    monkeypatch.setattr(log, "_current_level", 0)     # warnings on
    monkeypatch.setattr(H, "ACCEL_BACKENDS", ("cpu",))
    monkeypatch.setattr(FU, "_FUSED_PROBE", {})
    # run the kernels interpreted (they cannot compile here) and corrupt
    # the accumulate half's output
    real = FU._fused_call

    def wrong(*a, **kw):
        kw["interpret"] = True
        out = real(*a, **kw)
        if isinstance(out, tuple):
            return out[0] + 1, out[1]
        return out + 1
    monkeypatch.setattr(FU, "_fused_call", wrong)
    assert FU.fused_kernel_verified() is False
    assert "falls back to the staged kernel family" in capsys.readouterr().err


# ------------------------------------------------ entry points need the TPU

def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode != 0
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": False}
    reason = json.loads(lines[-2])
    assert reason["phase"] == "device" and "no TPU" in reason["reason"]
    assert not any('"ok": true' in ln for ln in lines)
