"""The persistent compilation cache of the entry points: an outside
``JAX_COMPILATION_CACHE_DIR`` wins, otherwise one fixed in-checkout path;
importing the library leaves the cache alone."""
import os
import subprocess
import sys

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _cache_dir_after(code: str, env_dir=None) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c",
         code + "\nimport jax; print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout.strip().splitlines()[-1]


def test_outside_cache_dir_is_used_as_is(tmp_path):
    code = ("from repro.runtime.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())")
    assert _cache_dir_after(code, str(tmp_path)) == str(tmp_path)


def test_default_cache_dir_is_fixed_inside_the_checkout():
    from repro.runtime.compile_cache import DEFAULT_CACHE_DIR
    code = ("from repro.runtime.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()")
    first, second = _cache_dir_after(code), _cache_dir_after(code)
    assert first == second == str(DEFAULT_CACHE_DIR)
    repo = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    assert os.path.commonpath([repo, first]) == repo


def test_library_import_leaves_the_cache_off():
    code = ("import repro.kernels.ops, repro.launch.serve, "
            "repro.launch.train")
    assert _cache_dir_after(code) == "None"
