"""Config-registry smoke tests: ARCHS stays in sync with the modules on
disk, every entry constructs (full and reduced), and the benchmark
driver's ``--list`` enumerates the registry (the operator-facing view)."""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import ARCHS, ArchConfig, get_arch

REPO = pathlib.Path(__file__).resolve().parent.parent
CONFIG_DIR = REPO / "src" / "repro" / "configs"
NON_ARCH_MODULES = {"__init__", "base"}


def test_every_config_module_is_registered():
    """Registry drift guard: a config module dropped into configs/ without
    an ARCHS entry is dead code — and an ARCHS entry whose module vanished
    is a broken import. Both directions must hold."""
    import importlib
    modules = {p.stem for p in CONFIG_DIR.glob("*.py")} - NON_ARCH_MODULES
    arch_configs = {id(cfg) for cfg in ARCHS.values()}
    for stem in sorted(modules):
        m = importlib.import_module(f"repro.configs.{stem}")
        assert hasattr(m, "CONFIG"), \
            f"configs/{stem}.py has no CONFIG — register it in ARCHS"
        assert id(m.CONFIG) in arch_configs, \
            f"configs/{stem}.py CONFIG is not in repro.configs.ARCHS"
    assert len(modules) == len(ARCHS), \
        (sorted(modules), sorted(ARCHS))


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_arch_constructs_and_reduces(name):
    """Every registered arch resolves, carries the fields --list prints,
    and produces a reduced variant that stays the same family (per-arch
    forward passes live in test_arch_smoke.py)."""
    cfg = get_arch(name)
    assert isinstance(cfg, ArchConfig)
    assert cfg.family and cfg.n_layers >= 1 and cfg.d_model >= 1
    red = cfg.reduced()
    assert isinstance(red, ArchConfig)
    assert red.family == cfg.family
    assert red.n_layers <= cfg.n_layers and red.d_model <= cfg.d_model


@pytest.mark.parametrize("name,reduced", [("llama3.2-1b", False),
                                          ("llama3.2-1b", True),
                                          ("paper-cnn", True)])
def test_train_cli_reduces_only_when_asked(name, reduced):
    """`launch/train.py --arch X` trains X at its published widths;
    `--reduced` selects the smoke-scale variant (the CNN has none)."""
    from repro.launch.train import arch_config
    cfg = arch_config(name, reduced)
    want = get_arch(name)
    if reduced and want.family != "cnn":
        want = want.reduced()
    assert cfg == want
    if name == "llama3.2-1b" and not reduced:
        assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (2048, 8192, 128256)


def test_get_arch_unknown_lists_choices():
    with pytest.raises(KeyError, match="paper-cnn"):
        get_arch("llama99-typo")


@pytest.mark.slow
def test_benchmarks_run_list_enumerates_configs():
    """`python -m benchmarks.run --list` prints the configs section with
    every registered arch (the operator's discovery surface — ISSUE 9
    satellite: configs are enumerable without reading source)."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--list"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "configs (archs):" in out.stdout
    for name in ARCHS:
        assert f"  {name} " in out.stdout, name
    assert "pool backends:" in out.stdout
    for backend in ("stacked", "moment", "lowrank"):
        assert f"  {backend}" in out.stdout
