"""Entry-point helpers: the compile-cache location and the serve CLI's
construction steps."""

import jax
import pytest

from repro.configs import get_config
from repro.launch import cache, serve


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_follows_the_env_else_the_checkout(monkeypatch, env):
    set_calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: set_calls.append(a))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = cache.enable_compile_cache()
    if env is None:
        # a fixed path inside the checkout, so the next process finds it
        assert got == str(cache.CACHE_DIR) and cache.CACHE_DIR.name == ".jax_cache"
        assert (cache.CACHE_DIR.parent / "src" / "repro").is_dir()
        assert set_calls == [("jax_compilation_cache_dir", got)]
    else:
        # JAX reads the variable itself: nothing is set in code
        assert got == env and set_calls == []


@pytest.mark.parametrize(
    "argv, dtype",
    [([], "bfloat16"), (["--dtype", "float32"], "float32")],
)
def test_serve_cli_keeps_the_config_dtype_unless_asked(argv, dtype):
    args = serve.parse_args(["--arch", "granite-8b", "--preset", "full", *argv])
    assert get_config("granite-8b").dtype == "bfloat16"
    assert serve.load_config(args).dtype == dtype
