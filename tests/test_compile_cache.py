"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache."""
import jax
import pytest

from repro.utils.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_directory(placed, tmp_path, monkeypatch,
                                 restore_cache_dir):
    """A directory placed from outside wins and nothing is set in code;
    otherwise the cache goes to the fixed, git-ignored in-repo directory."""
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    got = enable_compile_cache()
    if placed:
        assert got == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    else:
        root = REPO_CACHE_DIR.parent
        assert got == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert (root / "chip_smoke.py").is_file()
        assert "/.jax_cache/" in (root / ".gitignore").read_text().splitlines()
