"""The two rules every entry point shares (``parallel/distributed.py``):
one place for the compile cache, and no silent fall-back to the CPU."""

import os
import re

import jax
import pytest

import chip_smoke
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
    compilation_cache_dir,
    enable_compilation_cache,
    require_accelerator,
)

ROOT = chip_smoke.ROOT
ENTRY_POINTS = ("scripts/train.py", "scripts/serve.py", "scripts/predict.py",
                "chip_smoke.py")


def _tracked_python():
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in (
            "scratch_chip", "smoke_out", "chiprun_out", "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(base, name)


@pytest.fixture()
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    suite itself must stay cache-free)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_env_set_means_no_code_path_writes_the_cache_dir(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() == str(tmp_path)
    assert [k for k, _ in config_updates
            if k == "jax_compilation_cache_dir"] == []
    assert chip_smoke.cache_dir() == str(tmp_path)


def test_env_unset_means_one_fixed_directory_in_the_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert enable_compilation_cache() == fixed
    assert config_updates[0] == ("jax_compilation_cache_dir", fixed)
    assert compilation_cache_dir() == chip_smoke.cache_dir() == fixed
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_source_locations_lose_the_checkouts_path(monkeypatch,
                                                    config_updates):
    """A Mosaic kernel's module rides in its program with its source
    locations, and the compile cache's key is over those bytes: file
    names are made relative to the checkout, so the key is the same from
    a copy at another path (PR 29). A regex the user has set stays."""
    import re

    enable_compilation_cache()
    (regex,) = [v for k, v in config_updates
                if k == "jax_hlo_source_file_canonicalization_regex"]
    here = os.path.join(ROOT, "huggingface_sagemaker_tensorflow_distributed"
                        "_tpu", "ops", "pallas_paged_attention.py")
    assert re.sub(regex, "", here) == os.path.relpath(here, ROOT)
    assert re.sub(regex, "", "/elsewhere" + here) == "/elsewhere" + here
    config_updates.clear()
    monkeypatch.setattr(
        type(jax.config), "jax_hlo_source_file_canonicalization_regex",
        property(lambda self: "^/mine/"), raising=False)
    enable_compilation_cache()
    assert not [k for k, _ in config_updates
                if k == "jax_hlo_source_file_canonicalization_regex"]


def test_every_entry_point_goes_through_the_one_rule():
    """The directory is set in exactly one place, and every entry point
    calls it (no other spelling, no per-job or per-process path)."""
    setters = [p for p in _tracked_python()
               if "tests" not in os.path.relpath(p, ROOT).split(os.sep)
               and re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                             open(p).read())]
    assert [os.path.relpath(p, ROOT) for p in setters] == [
        "huggingface_sagemaker_tensorflow_distributed_tpu/parallel/"
        "distributed.py"]
    for entry in ENTRY_POINTS:
        text = open(os.path.join(ROOT, entry)).read()
        assert "enable_compilation_cache()" in text, entry
        assert "require_accelerator()" in text, entry
    for path in _tracked_python():
        text = open(path).read()
        if path.endswith(os.path.join("tests", "test_backend_rules.py")):
            continue
        assert "HSTD_COMPILE_CACHE_DIR" not in text, path
        assert "TPU_COMPILATION_CACHE_DIR" not in text, path


def test_cpu_named_runs():
    """The suite's conftest names the CPU (``jax_platforms``); the other
    side — a CPU backend nobody asked for is an error — needs a process
    of its own: ``tests/test_chip_smoke.py``."""
    device = require_accelerator()
    assert device["platform"] == "cpu"
    assert device["device_count"] == len(jax.devices())
    assert device["jax_version"] == jax.__version__
