"""``chip_smoke.py`` rehearsed on the CPU, and the persistent compile cache.

``chip_smoke.main`` is steered to 64 PEs and to the CPU, so phases A-C and
the CPU-reference comparison all run here; run as a script without a TPU
it must fail and print no result.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

import chip_smoke
from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_flags():
    """Restore the process-wide cache settings ``compile_cache.enable``
    changes, so later tests in this worker compile uncached as before."""
    saved = {f: getattr(jax.config, f) for f in _CACHE_FLAGS}
    yield
    for f, v in saved.items():
        jax.config.update(f, v)
    compilation_cache.reset_cache()


def test_chip_smoke_all_phases_at_64_pes_on_cpu(tmp_path, capsys,
                                                cache_flags):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    result = chip_smoke.main(n_pes=64, platform="cpu")
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    phases = {rec["phase"]: rec for rec in lines if "phase" in rec}
    assert sorted(phases) == ["A", "B", "C"]
    assert phases["A"]["points"] == 18 and phases["B"]["points"] == 6
    for rec in phases.values():
        assert rec["n_pes"] == 64
        assert rec["cpu_reference"]["equal"]
        assert rec["cpu_reference"]["points"] >= 1
    for fam, c in {**phases["B"]["counts"], **phases["C"]["counts"]}.items():
        assert c["lost"] == 0, fam
        assert c["offered"] == c["delivered"] + c["dropped"] + c["in_flight"]
    assert phases["C"]["counts"]["ring_mesh"]["dropped"] > 0  # faults bite
    # The cache landed where it was placed from outside, and nowhere else.
    cache = lines[-1]["compile_cache"]
    assert cache["dir"] == str(tmp_path) and cache["entries"] > 0
    assert cache["misses"] > 0
    assert result == {"ok": True, "device": {"platform": "cpu",
                                             "kind": "cpu", "count": 1}}


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_chip_smoke_script_fails_without_a_tpu(tmp_path, alone):
    """Without a TPU, and in a directory holding nothing of the repo but
    the script, it exits non-zero and prints no result."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert ("ModuleNotFoundError" if alone else "needs a tpu") in p.stderr


def test_compile_cache_default_dir_is_fixed_in_checkout(cache_flags):
    jax.config.update("jax_compilation_cache_dir", None)
    d = compile_cache.enable()
    assert d == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.enable() == d  # the same path every time


def test_compile_cache_unusable_dir_raises(tmp_path, cache_flags):
    blocker = tmp_path / "file"
    blocker.write_text("")
    jax.config.update("jax_compilation_cache_dir", str(blocker / "cache"))
    with pytest.raises(OSError):
        compile_cache.enable()


def test_compile_cache_second_process_hits(tmp_path, cache_flags):
    """A fresh process finds what an earlier one compiled."""
    code = ("import jax, jax.numpy as jnp, json\n"
            "from repro import compile_cache\n"
            "compile_cache.enable()\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
            "print(json.dumps(compile_cache.stats()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(ROOT, "src"))
    runs = [json.loads(subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120, check=True).stdout.splitlines()[-1])
        for _ in range(2)]
    assert runs[0]["dir"] == runs[1]["dir"] == str(tmp_path)
    assert runs[0]["misses"] >= 1 and runs[0]["hits"] == 0
    assert runs[1]["hits"] >= 1 and runs[1]["misses"] == 0
