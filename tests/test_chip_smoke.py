"""chip_smoke.py rehearsed on the CPU: its phase functions at
llama_tiny() size on the virtual devices of conftest.py (control flow and
results — which kernels a compiled program holds is the chip run's
check), the script's refusal of a CPU, and the plumbing it stands on:
the compile cache's place, and imports that leave the backend alone."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny():
    from paddle_tpu.models.llama import llama_tiny
    return llama_tiny(dtype="float32")


def test_train_phase_tiny(smoke):
    r = smoke.train_phase(_tiny(), seed=0, batch=2, seq=64, steps=4,
                          check_kernels=False)
    assert len(r["losses"]) == 4 and r["losses"][-1] < r["losses"][0]
    assert r["compile_s"] > 0 and len(r["warm_step_s"]) == 3


def test_serve_phase_tiny(smoke):
    r = smoke.serve_phase(_tiny(), seed=0, prompt_lens=(3, 9, 17, 30),
                          new_tokens=6, max_batch=2, max_seq=64,
                          buckets=(8, 16), check_kernels=False)
    assert r["tokens"] == 24
    # one float path on the CPU: no near-tie may be needed
    assert r["diverged"] == []


def test_sharded_phase_tiny(smoke):
    r = smoke.sharded_phase(_tiny(), seed=0, devices=jax.devices()[:4],
                            batch=4, seq=64, steps=4, check_kernels=False,
                            rtol=5e-3, atol=1e-5)
    assert r["drift"] <= 1           # f32 here: _dryrun_trajectory's bound
    assert len(r["held"]) == 4


def test_missing_kernel_is_an_error(smoke):
    text = ('%c = f32[8] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(f)/swiglu_fwd/'
            'pallas_call"}\n')
    assert smoke.kernels_in(text) == ["jit(f)/swiglu_fwd/pallas_call"]
    assert smoke.require_kernels(
        text, {"swiglu fwd": ("swiglu_fwd",)}, "t") == 1
    with pytest.raises(AssertionError, match="flash attention"):
        smoke.require_kernels(text, smoke.TRAIN_KERNELS, "t")


def _run(code_or_path, *argv, env=None, script=False):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    e.update(env or {})
    cmd = [sys.executable] + ([code_or_path] if script
                              else ["-c", code_or_path]) + list(argv)
    return subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                          env=e, timeout=300)


def test_script_refuses_a_cpu():
    p = _run(os.path.join(ROOT, "chip_smoke.py"), script=True)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("module", [
    "paddle_tpu", "paddle_tpu.distributed.launch.main",
    "paddle_tpu.inference.fleet", "paddle_tpu.framework.compile_cache"])
def test_import_initialises_no_backend(module):
    # a parent that has touched the backend holds the chip, and the
    # workers / replicas it starts then cannot have it
    p = _run(f"import {module}, jax._src.xla_bridge as xb; "
             f"assert not xb._backends, list(xb._backends)")
    assert p.returncode == 0, p.stderr


_CACHE_DIR = ("import jax; from paddle_tpu.framework.compile_cache import "
              "use_compile_cache; print(use_compile_cache()); "
              "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_defaults_to_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    p = subprocess.run([sys.executable, "-c", _CACHE_DIR],
                       capture_output=True, text=True, cwd="/",
                       env=dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
                       timeout=300)
    assert p.returncode == 0, p.stderr
    want = os.path.join(ROOT, ".jax_cache")
    assert p.stdout.split() == [want, want]


def test_compile_cache_honours_the_environment(tmp_path):
    p = _run(_CACHE_DIR, env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_a_stale_native_library_is_rebuilt_not_trusted(tmp_path):
    # the .so files are git-ignored: one left on disk by another build
    # (whatever its mtime) is used only if its stamp matches the source
    import shutil

    from paddle_tpu.utils import _native_build as nb
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src, built = tmp_path / "f.cpp", tmp_path / "libf1.so"
    src.write_text('extern "C" int f() { return 1; }\n')
    assert nb.build_and_load(str(src), str(built)).f() == 1
    # a newer-looking library built from an older source, stamp and all
    # (a fresh path each time: dlopen keeps a loaded path's old handle)
    left = tmp_path / "libf2.so"
    shutil.copy(built, left)
    shutil.copy(str(built) + ".src", str(left) + ".src")
    os.utime(left, (2 ** 31, 2 ** 31))
    src.write_text('extern "C" int f() { return 2; }\n')
    assert nb.build_and_load(str(src), str(left)).f() == 2
    # and one with no stamp at all
    bare = tmp_path / "libf3.so"
    shutil.copy(built, bare)
    assert nb.build_and_load(str(src), str(bare)).f() == 2


def test_launcher_pins_its_master_to_the_cpu_and_not_its_workers(
        monkeypatch, tmp_path):
    # the elastic master must never take a chip; the environment the
    # workers are started from must come through untouched
    import types

    from paddle_tpu.distributed.launch import main as launch
    seen = {}
    monkeypatch.setattr(
        launch.subprocess, "Popen",
        lambda cmd, env=None, **kw: seen.update(cmd=cmd, env=env))
    env = {"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "tpu"}
    launch._spawn_master(types.SimpleNamespace(log_dir=None), env,
                         "127.0.0.1:1", 2, 0,
                         journal=str(tmp_path / "journal"))
    assert seen["cmd"][-1] == "paddle_tpu.distributed.elastic_master"
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert env["JAX_PLATFORMS"] == "tpu" and "PYTHONPATH" not in env


def test_train_step_lowers_the_same_program_in_every_process():
    # optimizer state crosses the jit boundary keyed by parameter name:
    # an id() in a pytree key would name the outputs, and order the
    # arguments, by memory address — and no second process would ever
    # find the step in the persistent compile cache
    code = (
        "import hashlib, numpy as np, paddle_tpu as paddle\n"
        "import paddle_tpu.optimizer as popt\n"
        "from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny\n"
        "paddle.seed(0); m = LlamaForCausalLM(llama_tiny())\n"
        "o = popt.AdamW(learning_rate=1e-3, parameters=m.parameters())\n"
        "s = paddle.jit.TrainStep(m, o, lambda i, l: m.loss(i, l))\n"
        "x = paddle.to_tensor(np.zeros((2, 16), np.int32))\n"
        "t = s.lower(x, x).as_text()\n"
        "assert \"('lm_head', 'moment1')\" in t\n"
        "print(hashlib.sha256(t.encode()).hexdigest())\n")
    a, b = _run(code), _run(code)
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    assert a.stdout == b.stdout
