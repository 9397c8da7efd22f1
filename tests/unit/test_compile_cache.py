"""The one helper that places the persistent compile cache, and the two
process rules around it: a launcher parent exports the variable instead of
touching JAX, and a serving worker with a real engine inherits the
platform."""

import pathlib

import jax
import pytest

from deepspeed_tpu.utils import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them: the
    suite itself must keep running without a persistent cache."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_variable_wins_and_nothing_is_set_in_code(monkeypatch,
                                                      config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    assert config_updates == []


def test_unset_points_inside_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    checkout = pathlib.Path(__file__).resolve().parents[2]
    assert compile_cache.configure_compile_cache() == str(
        checkout / ".jax_cache")
    (name, value), = config_updates
    assert name.endswith("compilation_cache_dir")
    assert value == compile_cache.DEFAULT_DIR == str(checkout / ".jax_cache")
    assert ".jax_cache/" in (checkout / ".gitignore").read_text()


def test_launcher_exports_the_cache_to_its_children(monkeypatch):
    from deepspeed_tpu.launcher.multinode_runner import rank_env

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert (rank_env(0, 2, "10.0.0.1", 1234)[compile_cache.ENV_VAR]
            == compile_cache.DEFAULT_DIR)
    monkeypatch.setenv(compile_cache.ENV_VAR, "/shared/cache")
    assert rank_env(1, 2, "10.0.0.1", 1234)[compile_cache.ENV_VAR] \
        == "/shared/cache"


def test_real_engine_workers_inherit_the_platform(monkeypatch):
    """Only host-only (synthetic) workers are pinned to the CPU; a worker
    with a real engine started on a chip host must not quietly serve from
    the CPU."""
    from deepspeed_tpu.launcher.serving_fleet import _worker_env

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert _worker_env("synthetic", None)["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in _worker_env("tiny-llama", None)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert _worker_env("synthetic", None)["JAX_PLATFORMS"] == "tpu"
    assert _worker_env("tiny-llama", {"TPU_VISIBLE_CHIPS": "2"})[
        "TPU_VISIBLE_CHIPS"] == "2"
