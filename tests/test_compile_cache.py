"""The placeable compile cache (bigdl_tpu/utils/compile_cache.py) and the
rule that no library path carries on when the backend does not come up.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import jax\n"
    "from bigdl_tpu.utils.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "print(before, enable_compile_cache(),\n"
    "      jax.config.jax_compilation_cache_dir)\n")


def _fresh_interpreter(cache_env, cwd):
    """What a fresh process that owns its entry point sees: (directory
    JAX held before the helper ran, the helper's answer, the directory
    JAX holds after)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_unset_the_cache_is_the_fixed_in_checkout_path(tmp_path):
    """Unset, the directory is <checkout>/.jax_cache — derived from the
    package's location, so two fresh interpreters started in different
    directories agree on it (the path is part of the cache key: a
    directory that moves never hits)."""
    want = os.path.join(REPO, ".jax_cache")
    a = _fresh_interpreter(None, cwd=str(tmp_path))
    b = _fresh_interpreter(None, cwd=REPO)
    assert a == b == ["None", want, want]


def test_set_from_outside_no_directory_is_set_in_code(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set JAX reads it by itself; the
    helper reports that directory and sets no other."""
    placed = str(tmp_path / "placed")
    got = _fresh_interpreter(placed, cwd=str(tmp_path))
    assert got == [placed, placed, placed]


def test_helper_sets_no_directory_when_the_variable_is_set(monkeypatch):
    import jax
    from bigdl_tpu.utils import compile_cache
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append(name))
    assert compile_cache.enable_compile_cache() == "/placed/elsewhere"
    assert calls == []


def test_nothing_turns_the_cache_on_at_import():
    """Importing the package (the tests have, long before this one) sets
    no cache directory: only entry points that own a process do."""
    import jax
    import bigdl_tpu  # noqa: F401
    import bigdl_tpu.examples.perf  # noqa: F401
    import bigdl_tpu.serving.__main__  # noqa: F401
    assert jax.config.jax_compilation_cache_dir is None


def test_default_cache_dir_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()


def test_engine_init_lets_a_backend_error_through(monkeypatch):
    """A chip that fails to come up must not read as "1 node, 1
    device"."""
    import jax
    from bigdl_tpu.utils.engine import Engine

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "local_device_count", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        Engine.init()
    monkeypatch.undo()
    Engine.init()
    assert Engine.local_device_count() == jax.local_device_count()
