"""Per-module flops profiler (VERDICT round-3 item 7).

Reference: ``profiling/flops_profiler/profiler.py`` per-module
MACs/params/latency table honoring ``module_depth``/``top_modules``
(SURVEY §2.5) — "which layer burns the FLOPs" must be answerable."""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.profiling.flops_profiler.profiler import (
    format_module_table, profile_model_modules)


def _model_and_batch():
    cfg = LlamaConfig.tiny(num_layers=3, dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.asarray(
        np.random.RandomState(0).randint(0, 512, size=(8, 32)))}
    return model, params, batch


def test_per_module_table_depth_and_totals():
    model, params, batch = _model_and_batch()
    rows = profile_model_modules(model, params, batch)
    # depth-1 protocol modules + depth-2 submodules
    assert {"embed", "layers", "head"} <= set(rows)
    assert {"layers.attn", "layers.mlp"} <= set(rows)
    assert rows["layers"]["count"] == 3
    assert all(r["flops"] > 0 for r in rows.values())
    # depth-1 latency percentages cover the whole step
    d1 = sum(r["pct_latency"] for r in rows.values() if r["depth"] == 1)
    np.testing.assert_allclose(d1, 100.0, rtol=1e-6)
    # the trunk must dominate a 3-layer model's forward
    assert rows["layers"]["pct_latency"] > rows["embed"]["pct_latency"]
    # attn + mlp ≈ one decoder layer's flops (residuals/norms are noise)
    sub = rows["layers.attn"]["flops"] + rows["layers.mlp"]["flops"]
    assert 0.8 * rows["layers"]["flops"] < sub < 1.2 * rows["layers"]["flops"]
    text = format_module_table(rows)
    assert "layers x3" in text and "% latency" in text


def test_top_modules_filter():
    model, params, batch = _model_and_batch()
    rows = profile_model_modules(model, params, batch, top_modules=1)
    assert len([n for n, r in rows.items() if r["depth"] == 1]) == 1
    # the single kept depth-1 row is the most expensive one
    assert "layers" in rows


def test_engine_emits_table_at_profile_step(tmp_path):
    out = tmp_path / "profile.txt"
    model, params, batch = _model_and_batch()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "flops_profiler": {"enabled": True, "profile_step": 2,
                                   "output_file": str(out)},
                "steps_per_print": 0})
    engine.train_step(batch)
    assert not out.exists()  # step 1 < profile_step
    engine.train_step(batch)
    assert out.exists()
    text = out.read_text()
    assert "embed" in text and "layers" in text and "head" in text
