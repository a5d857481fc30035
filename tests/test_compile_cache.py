"""The persistent compile cache the entry points turn on
(``repro.launch.compile_cache``). Each case runs in a subprocess, so the
test process itself never turns the cache on."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

SCRIPT = """
import json, jax
import repro.launch.serve, repro.launch.train   # importing turns nothing on
before = jax.config.jax_compilation_cache_dir
from repro.launch.compile_cache import enable_compile_cache
first, second = enable_compile_cache(), enable_compile_cache()
print(json.dumps([before, first, second, jax.config.jax_compilation_cache_dir]))
"""


def _run(cwd, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_honours_the_environment(tmp_path):
    before, first, second, config = _run(ROOT, **{ENV_VAR: str(tmp_path)})
    assert first == second == config == before == str(tmp_path)


def test_cache_dir_is_one_fixed_path_in_the_checkout(tmp_path):
    runs = [_run(ROOT), _run(tmp_path)]     # two processes, two cwds
    fixed = str(ROOT / ".jax_cache")
    for before, first, second, config in runs:
        assert before is None               # off until main() asks
        assert first == second == config == fixed
