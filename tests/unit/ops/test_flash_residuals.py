"""What remat keeps of the flash attention call: the op names its ``out``
and ``lse`` where its rule says they are dearer to recompute than a
projection's output (``keeps_residuals``, from the call's shapes), the
layer bodies' one policy (``remat_policy``) holds what is named, and the
forward then runs once a layer and not a second time inside the
backward."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.runtime.activation_checkpointing import remat_policy
from deepspeed_tpu.utils import groups

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
llama = importlib.import_module("deepspeed_tpu.models.llama")


@pytest.fixture(autouse=True)
def _hub_left_as_found():
    """Tests here switch the process's telemetry hub on (by hand, or by
    an engine built with ``telemetry.enabled``); left on, it counts into
    whatever file this worker runs next."""
    yield
    from deepspeed_tpu.telemetry import get_telemetry

    get_telemetry().reset()


@pytest.mark.parametrize("S, h, d, causal, window, keys, kept", [
    # the Mistral training cells' row: 6,144 operations a byte of ``out``
    # against the projections' 4,096
    (8192, 32, 128, True, 4096, 3072.25, True),
    # BERT-large's: 1,024 against 1,024, no dearer than what is held already
    (512, 16, 64, False, None, 512.0, False),
    # a window much shorter than the row: the window decides, not the row
    (8192, 32, 128, True, 1024, 960.0625, False),
    # the same row with no window, and Mistral's under a split of its
    # heads over 4 chips (what a device's call is given)
    (8192, 32, 128, True, None, 4096.5, True),
    (8192, 8, 128, True, 1024, 960.0625, True),
    # a window on both sides of a row that is not causal
    (512, 16, 64, False, 128, 223.25, False),
    (256, 8, 16, True, None, 128.5, True),          # LlamaConfig.tiny()
    (64, 8, 16, True, None, 32.5, False),
], ids=["mistral7b", "bert_large", "short_window", "mistral7b_no_window",
        "short_window_tp4", "window_both_sides", "tiny_256", "tiny_64"])
def test_keep_or_recompute_is_a_function_of_the_shapes(S, h, d, causal,
                                                       window, keys, kept):
    """The rule: keep where ``2 × keys scored a row`` (operations a byte of
    ``out``) is strictly more than ``h·d`` (operations a byte of a
    projection's output, which the dots policy holds already); and the two
    counters tick the way it went, one a traced call."""
    from deepspeed_tpu.telemetry import get_telemetry

    assert fa.mean_keys_scored(S, causal, window) == keys
    # the count is the position mask's own
    if S <= 512:
        from deepspeed_tpu.ops.masks import local_attention_mask

        pos = jnp.arange(S)
        assert float(local_attention_mask(
            pos, pos, causal=causal, window=window).sum()) / S == keys
    assert fa.keeps_residuals(S, h, d, causal, window) is kept
    assert (2 * keys > h * d) is kept

    hub = get_telemetry()
    hub.reset()
    hub.configure(enabled=True, jsonl=False, prometheus=False)
    x = jax.ShapeDtypeStruct((1, S, h, d), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: fa.flash_attention(
        q, k, v, causal, window=window), x, x, x)
    counts = {name.rsplit("/", 1)[1]: c["value"] for name, c in
              hub.registry.snapshot()["counters"].items()
              if name.startswith("ops/flash_attention/residuals_")}
    assert counts == {
        "residuals_kept" if kept else "residuals_recomputed": 1.0}


def _tiny_step(S, meshed, window=None, layers=2):
    mesh = (groups.initialize_mesh(MeshLayout.infer(8, dp=8)) if meshed
            else None)
    cfg = LlamaConfig.tiny(num_layers=layers, dtype=jnp.float32, remat=True,
                           attn_impl="flash", max_seq_len=S,
                           sliding_window=window)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(8, S)))
    return model, params, {"input_ids": ids}


_SIDES = [(256, None, True), (64, None, False), (256, 32, False)]
_SIDE_IDS = ["S256_kept", "S64_recomputed", "S256_window32_recomputed"]


@pytest.mark.parametrize("meshed", [False, True], ids=["no_mesh", "dp8"])
@pytest.mark.parametrize("S, window, kept", _SIDES, ids=_SIDE_IDS)
def test_gradient_holds_the_attention_forward_once_where_kept(
        S, window, kept, meshed, monkeypatch):
    """The gradient's jaxpr of a tiny Llama under ``remat=True``, the
    kernels in the interpreter so that the forward has a marker of its
    own (the ``pallas_call`` named ``flash_fwd``): once a layer scan where
    the rule keeps its outputs (the forward pass's; the backward's scan
    body reads the held ``out`` and ``lse``), twice where it does not
    (remat's recomputation beside it); through ``flash_attention_spmd``'s
    ``shard_map`` as without a mesh; the backward kernel once either
    way."""
    monkeypatch.setattr(fa, "flash_route",
                        lambda *a, **k: ("interpret", None))
    model, params, batch = _tiny_step(S, meshed, window)
    assert model.keeps_flash_residuals() is kept
    text = str(jax.make_jaxpr(jax.value_and_grad(model.loss))(params, batch))
    assert text.count("name=flash_fwd") == (1 if kept else 2)
    assert text.count("name=flash_bwd") == 1
    names = [n for n in fa.RESIDUAL_NAMES if f"name[name={n}]" in text]
    assert names == (list(fa.RESIDUAL_NAMES) if kept else [])

    # under the dots-only policy, the parent's, the forward is always twice
    monkeypatch.setattr(
        llama, "remat_policy",
        lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    text = str(jax.make_jaxpr(jax.value_and_grad(model.loss))(params, batch))
    assert text.count("name=flash_fwd") == 2


@pytest.mark.parametrize("meshed", [False, True], ids=["no_mesh", "dp8"])
@pytest.mark.parametrize("S, window, kept", _SIDES, ids=_SIDE_IDS)
def test_gradients_equal_the_dots_only_policys_bit_for_bit(
        S, window, kept, meshed, monkeypatch):
    """Keeping ``out`` and ``lse`` changes what is recomputed, not what is
    computed: loss and every gradient leaf equal the dots-only policy's to
    the bit (the reference route, which carries the same names)."""
    model, params, batch = _tiny_step(S, meshed, window)
    step = lambda: jax.jit(jax.value_and_grad(model.loss))(params, batch)
    loss, grads = step()
    monkeypatch.setattr(
        llama, "remat_policy",
        lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    loss_dots, grads_dots = step()
    assert float(loss) == float(loss_dots) and np.isfinite(float(loss))
    flat, flat_dots = (jax.tree_util.tree_leaves_with_path(g)
                       for g in (grads, grads_dots))
    assert len(flat) == len(flat_dots) > 0
    for (path, g), (_, g_dots) in zip(flat, flat_dots):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_dots),
                                      err_msg=jax.tree_util.keystr(path))


def test_every_layer_body_shares_the_one_policy():
    """``models/llama.py``, ``models/bert.py``, ``models/opt.py`` and
    ``checkpointing._policy`` take their policy from ``remat_policy``: no
    second copy of the dots policy is written anywhere in the package."""
    import pathlib

    import deepspeed_tpu
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing

    root = pathlib.Path(deepspeed_tpu.__file__).parent
    holders = sorted(
        str(p.relative_to(root)) for p in root.rglob("*.py")
        if "dots_with_no_batch_dims_saveable" in p.read_text())
    assert holders == ["runtime/activation_checkpointing/checkpointing.py"]
    assert checkpointing._policy().__qualname__ == \
        remat_policy().__qualname__


@pytest.mark.parametrize("S, kept", [(256, True), (64, False)],
                         ids=["kept", "recomputed"])
def test_memory_ledger_counts_what_remat_holds(S, kept, monkeypatch):
    """``engine/flash_softmax_stats``: one layer's lse and delta under
    remat, plus every layer's ``out`` and ``lse`` where the op's rule (asked
    through the module) keeps them."""
    import deepspeed_tpu
    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    monkeypatch.setattr(fa, "flash_route", lambda *a, **k: ("kernel", None))
    get_memory_ledger().reset()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    cfg = LlamaConfig.tiny(num_layers=3, attn_impl="flash", max_seq_len=S)
    model = LlamaModel(cfg, mesh=mesh)
    assert model.keeps_flash_residuals() is kept
    eng, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "telemetry": {"enabled": True, "jsonl": False,
                              "prometheus": False}}, mesh=mesh)
    entry, = [e for e in (eng.memory_ledger or get_memory_ledger()).entries()
              if e["key"] == "engine/flash_softmax_stats"]
    get_memory_ledger().reset()
    stats = 2 * cfg.num_heads * S * 4                # rows × heads × S, f32
    out = 2 * S * cfg.num_heads * cfg.hd * np.dtype(cfg.dtype).itemsize
    assert entry["nbytes"] == 2 * stats + (3 * (out + stats) if kept else 0)
