"""Guards of the entry points that run on the chip, checked on the CPU:
chip_smoke.py refuses a non-TPU backend, the compile cache lands where
it should, and the depth cut keeps whole periods of the layer pattern."""
import argparse
import importlib.util
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.args import add_model_args, model_config_from_args  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------- chip_smoke.py
def test_chip_smoke_device_check_refuses_cpu():
    cs = _chip_smoke()
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(cs.SmokeFailure, match="needs a TPU"):
        cs.require_tpu()


def test_chip_smoke_main_fails_before_any_phase_on_cpu(capsys, monkeypatch):
    cs = _chip_smoke()
    ran = []
    for phase in ("phase_train", "phase_serve", "phase_svm"):
        monkeypatch.setattr(cs, phase, lambda *a, _p=phase: ran.append(_p))
    with pytest.raises(cs.SmokeFailure):
        cs.main([])
    assert ran == []
    assert '"ok"' not in capsys.readouterr().out


# ----------------------------------------------------------- compile cache
@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # never moves
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


# --------------------------------------------------------------- --layers
def _model_args(argv):
    return add_model_args(argparse.ArgumentParser(), "granite-3-8b").parse_args(
        argv
    )


@pytest.mark.parametrize(
    "arch,layers,stages",
    [
        ("granite-3-8b", 1, ((("attn",), 1),)),
        ("granite-3-8b", 40, ((("attn",), 40),)),
        ("recurrentgemma-2b", 3, ((("rglru", "rglru", "local_attn"), 1),)),
        ("recurrentgemma-2b", 26, (
            (("rglru", "rglru", "local_attn"), 8), (("rglru", "rglru"), 1),
        )),
    ],
)
def test_layers_keeps_whole_periods_and_widths(arch, layers, stages):
    full = get_config(arch)
    cfg = model_config_from_args(
        _model_args(["--arch", arch, "--layers", str(layers)])
    )
    assert cfg.stages == stages
    assert cfg.num_layers == layers
    assert cfg.replace(stages=full.stages) == full  # no width changed


@pytest.mark.parametrize(
    "arch,layers",
    [("recurrentgemma-2b", 4), ("recurrentgemma-2b", 25),
     ("granite-3-8b", 41), ("granite-3-8b", -1)],
)
def test_layers_refuses_partial_period(arch, layers):
    args = _model_args(["--arch", arch, "--layers", str(layers)])
    with pytest.raises(ValueError, match="layers="):
        model_config_from_args(args)
