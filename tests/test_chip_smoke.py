"""CPU rehearsal of ``chip_smoke.py`` at ``rnnt-crdnn-smoke`` size: the
same phases and checks as on the chip, minus what only a TPU can decide
(compiled Pallas kernels; ``main()`` alone asserts the platform).  Also
the compile-cache helper the entry points call."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.launch.cache import DEFAULT_CACHE_DIR, enable_compile_cache  # noqa: E402


def test_one_chip_flow_at_smoke_size():
    lines = []
    facts = chip_smoke.one_chip(chip_smoke.SMOKE, interpret=True,
                                log=lines.append)
    # off the chip "auto" resolves to the XLA paths; main() refuses that
    assert facts == {"custom_call": False, "selection_kernels": ["xla"]}
    text = "\n".join(lines)
    for want in ("epoch 0: train", "epoch 2: train", "selected ",
                 "lattice (8, 8, 9) vs ref", "gram (4, 4, 4096) vs ref",
                 "fused loss on cpu", " compiles, "):
        assert want in text, (want, text)


def test_smoke_run_trains_and_selects():
    run = chip_smoke.train(chip_smoke.SMOKE, log=lambda s: None)
    assert run["train_loss"][-1] < run["train_loss"][0]
    assert 0 < run["selected_units"] < run["n_units"]
    assert run["selection_kernels"] == "xla"


@pytest.mark.slow
def test_four_chip_flow_on_host_devices():
    """The --four-chips phase on four forced host devices: placement
    over all four, and per-epoch losses within the mesh tolerance."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke as cs\n"
            "f = cs.four_chips(cs.SMOKE)\n"
            "print('FACTS', f)\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "FACTS {'custom_call': False, 'selection_kernels': ['xla', " \
           "'xla']}" in p.stdout
    assert "units: 16 rows over 4 devices, 4 on device 0" in p.stdout
    assert "batch: 8 rows over 4 devices, 2 on device 0" in p.stdout


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_compile_cache_default_is_inside_the_checkout():
    assert DEFAULT_CACHE_DIR == os.path.join(os.path.abspath(ROOT),
                                             ".jax_cache")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_entries_land_where_configured(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; without it
    the helper's default directory is used."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    target = tmp_path / ("env" if from_env else "default")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(target)
        call = "enable_compile_cache()"
    else:
        call = f"enable_compile_cache({str(target)!r})"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.cache import enable_compile_cache\n"
            f"print('DIR', {call})\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_"
            "ready()\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    assert f"DIR {target}" in p.stdout
    assert any(n.endswith("-cache") for n in os.listdir(target))


def test_compile_cache_helper_leaves_env_dir_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache("/unused") == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
