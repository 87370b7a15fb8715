"""Device/platform runtime (L0): the import leaves the chip alone, and
nothing resolves a missing accelerator or a bad index to something else.
"""
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_initialises_no_backend():
    """A launcher parent must be able to import the package and still
    leave the chip to the worker it spawns: after ``import paddle_tpu``
    no JAX backend exists and no platform was rewritten."""
    code = (
        "import os\n"
        "import paddle_tpu\n"
        "import paddle_tpu.distributed.launch.main\n"
        "from jax._src import xla_bridge\n"
        "import jax\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "assert os.environ['JAX_PLATFORMS'] == 'cpu'\n"
        "print('platform', jax.devices()[0].platform)\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "platform cpu"


@pytest.mark.parametrize("name", ["tpu", "tpu:0", "gpu", "gpu:0", "cuda",
                                  "xpu", "npu"])
def test_set_device_accelerator_raises_on_cpu_backend(name):
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="default backend is 'cpu'"):
        paddle.set_device(name)
    assert paddle.get_device() == before


def test_set_device_index_out_of_range_raises():
    import jax

    n = len(jax.devices("cpu"))
    with pytest.raises(ValueError, match="out of range"):
        paddle.set_device(f"cpu:{n}")
    assert paddle.set_device(f"cpu:{n - 1}").get_device_id() == n - 1
    paddle.set_device("cpu")


def test_set_device_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown device"):
        paddle.set_device("quantum:0")


def test_on_tpu_is_the_backend_name():
    from paddle_tpu.ops import kernels

    assert kernels.on_tpu() is False
    assert kernels.use_pallas() is False


def test_synchronize_lets_errors_through(monkeypatch):
    import jax

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(jax, "device_put", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        paddle.device.synchronize()


def test_compile_cache_directory(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the repo sets no
    directory. Unset: one fixed directory inside the checkout — wired by
    the serving entry (PagedKVCacheManager) as well as by to_static."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from paddle_tpu.incubate.nn import PagedKVCacheManager\n"
        "updates = []\n"
        "orig = jax.config.update\n"
        "def spy(k, v):\n"
        "    updates.append(k)\n"
        "    return orig(k, v)\n"
        "jax.config.update = spy\n"
        "PagedKVCacheManager(4, 16, 2, 128, dtype=jnp.float32)\n"
        "print(int('jax_compilation_cache_dir' in updates))\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("FLAGS_compilation_cache_dir", None)

    def run(cache_env):
        e = dict(env)
        e.pop("JAX_COMPILATION_CACHE_DIR", None)
        if cache_env:
            e["JAX_COMPILATION_CACHE_DIR"] = cache_env
        r = subprocess.run([sys.executable, "-c", code], env=e, cwd=REPO,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.strip().splitlines()[-2:]

    outside = str(tmp_path / "cache")
    assert run(outside) == ["0", outside]
    assert run(None) == ["1", os.path.join(REPO, ".jax_cache")]
