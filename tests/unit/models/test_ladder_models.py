"""Driver config-ladder rungs 1-2: CIFAR ResNet + BERT encoder, plus the
HF Llama checkpoint importer (SURVEY §7 hard-part 4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import (BertConfig, BertModel, LlamaModel,
                                  ResNetConfig, ResNetModel)
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


# ---------------------------------------------------------------------------
# ResNet (ladder rung 1 — ZeRO-0)
# ---------------------------------------------------------------------------

def test_resnet_forward_and_param_count():
    cfg = ResNetConfig.tiny(dtype=jnp.float32)
    model = ResNetModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    images = jnp.asarray(np.random.RandomState(0).randn(
        4, cfg.image_size, cfg.image_size, 3).astype(np.float32))
    logits = model.forward(params, images)
    assert logits.shape == (4, cfg.num_classes)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_resnet56_depth_math():
    assert ResNetConfig.resnet56().blocks_per_stage == 9
    with pytest.raises(ValueError):
        ResNetConfig(depth=57).blocks_per_stage


def test_resnet_trains_through_engine():
    """Ladder config 1: ZeRO-0 single-ish mesh; loss decreases."""
    cfg = ResNetConfig.tiny(dtype=jnp.float32)
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    model = ResNetModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    batch = {"images": jnp.asarray(rng.randn(
        8, cfg.image_size, cfg.image_size, 3).astype(np.float32)),
        "labels": jnp.asarray(rng.randint(0, cfg.num_classes, size=(8,)))}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 0})
    first = float(engine.train_step(batch)["loss"])
    for _ in range(8):
        last = float(engine.train_step(batch)["loss"])
    assert last < first


# ---------------------------------------------------------------------------
# BERT (ladder rung 2 — ZeRO-1/2)
# ---------------------------------------------------------------------------

def _mlm_batch(cfg, rng, batch=8, seq=32):
    ids = rng.randint(4, cfg.vocab_size, size=(batch, seq))
    labels = np.full_like(ids, -100)
    mask_pos = rng.rand(batch, seq) < 0.15
    labels[mask_pos] = ids[mask_pos]
    ids[mask_pos] = 3  # [MASK]
    return {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}


def test_bert_forward_and_param_count():
    cfg = BertConfig.tiny(dtype=jnp.float32)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _mlm_batch(cfg, np.random.RandomState(0))
    logits = model.forward(params, batch["input_ids"])
    assert logits.shape == (8, 32, cfg.vocab_size)
    loss = model.loss(params, batch)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.2
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_bert_attention_mask_blocks_padding():
    """Padded positions must not influence other tokens' logits."""
    cfg = BertConfig.tiny(num_layers=2, dtype=jnp.float32)
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    ids = rng.randint(4, cfg.vocab_size, size=(2, 16))
    mask = np.ones((2, 16), np.int32)
    mask[:, 12:] = 0
    a = model.forward(params, jnp.asarray(ids), jnp.asarray(mask))
    ids2 = ids.copy()
    ids2[:, 12:] = rng.randint(4, cfg.vocab_size, size=(2, 4))
    b = model.forward(params, jnp.asarray(ids2), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(a[:, :12]), np.asarray(b[:, :12]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stage", [1, 2])
def test_bert_trains_zero_stage_1_2(stage):
    """Ladder config 2: BERT under ZeRO-1/2 on the 8-device mesh."""
    cfg = BertConfig.tiny(num_layers=2, dtype=jnp.float32)
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    model = BertModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _mlm_batch(cfg, np.random.RandomState(3))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": stage},
                "steps_per_print": 0})
    first = float(engine.train_step(batch)["loss"])
    for _ in range(8):
        last = float(engine.train_step(batch)["loss"])
    assert last < first


# ---------------------------------------------------------------------------
# HF Llama import
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_hf_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    path = tmp_path_factory.mktemp("hf_llama")
    hf_cfg = HFLlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg)
    model.save_pretrained(path)
    return str(path)


def test_hf_llama_import_logits_match(tiny_hf_checkpoint):
    """Imported params reproduce the HF torch model's logits."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaForCausalLM

    from deepspeed_tpu.models.hf_import import load_hf_llama

    config, params = load_hf_llama(tiny_hf_checkpoint,
                                   dtype=jnp.float32, remat=False)
    assert config.num_layers == 2 and config.num_kv_heads == 2
    model = LlamaModel(config)

    ids = np.random.RandomState(5).randint(0, 128, size=(2, 10))
    ours = model.forward(params, jnp.asarray(ids))

    hf = LlamaForCausalLM.from_pretrained(tiny_hf_checkpoint,
                                          attn_implementation="eager")
    hf.eval()
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=2e-4, atol=2e-4)


def test_hf_import_tied_embeddings(tiny_hf_checkpoint):
    """tie_word_embeddings → no lm_head leaf; head reuses embed.T."""
    from deepspeed_tpu.models.hf_import import load_hf_llama

    config, params = load_hf_llama(tiny_hf_checkpoint, dtype=jnp.float32,
                                   tie_embeddings=True)
    assert "lm_head" not in params
    model = LlamaModel(config)
    ids = jnp.asarray([[1, 2, 3]])
    logits = model.forward(params, ids)
    assert logits.shape == (1, 3, 128)


# ---------------------------------------------------------------------------
# HF import breadth (VERDICT r3 item 9): Mistral / Mixtral / OPT / BERT
# follow the same logits-match-torch pattern as Llama above
# ---------------------------------------------------------------------------

def test_hf_mistral_import_logits_match(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import MistralConfig as HFMistralConfig
    from transformers import MistralForCausalLM

    from deepspeed_tpu.models.hf_import import load_hf_mistral

    hf_cfg = HFMistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=16,
        attn_implementation="eager")
    torch.manual_seed(0)
    hf = MistralForCausalLM(hf_cfg)
    hf.save_pretrained(tmp_path)

    config, params = load_hf_mistral(str(tmp_path), dtype=jnp.float32,
                                     remat=False)
    assert config.sliding_window == 16
    model = LlamaModel(config)
    ids = np.random.RandomState(5).randint(0, 128, size=(2, 10))
    ours = model.forward(params, jnp.asarray(ids))
    hf.eval()
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs,
                               rtol=2e-4, atol=2e-4)


def test_hf_mixtral_import_logits_match(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig as HFMixtralConfig
    from transformers import MixtralForCausalLM

    from deepspeed_tpu.models import MixtralModel
    from deepspeed_tpu.models.hf_import import load_hf_mixtral

    hf_cfg = HFMixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, attn_implementation="eager")
    torch.manual_seed(0)
    hf = MixtralForCausalLM(hf_cfg)
    hf.save_pretrained(tmp_path)

    # generous capacity: HF routes every token to its top-k with no drops
    config, params = load_hf_mixtral(str(tmp_path), dtype=jnp.float32,
                                     remat=False, capacity_factor=100.0)
    assert config.num_experts == 4 and config.top_k == 2
    model = MixtralModel(config)
    ids = np.random.RandomState(5).randint(0, 128, size=(2, 10))
    ours = model.forward(params, jnp.asarray(ids))
    hf.eval()
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs,
                               rtol=5e-4, atol=5e-4)


def test_hf_opt_import_logits_match(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import OPTConfig as HFOPTConfig
    from transformers import OPTForCausalLM

    from deepspeed_tpu.models import OPTModel
    from deepspeed_tpu.models.hf_import import load_hf_opt

    hf_cfg = HFOPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, word_embed_proj_dim=64)
    torch.manual_seed(0)
    hf = OPTForCausalLM(hf_cfg)
    hf.save_pretrained(tmp_path)

    config, params = load_hf_opt(str(tmp_path), dtype=jnp.float32,
                                 remat=False)
    model = OPTModel(config)
    ids = np.random.RandomState(5).randint(0, 128, size=(2, 10))
    ours = model.forward(params, jnp.asarray(ids))
    hf.eval()
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs,
                               rtol=2e-4, atol=2e-4)


def test_hf_bert_import_logits_match(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import BertConfig as HFBertConfig
    from transformers import BertForMaskedLM

    from deepspeed_tpu.models import BertModel
    from deepspeed_tpu.models.hf_import import load_hf_bert

    hf_cfg = HFBertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, type_vocab_size=2,
        attn_implementation="eager")
    torch.manual_seed(0)
    hf = BertForMaskedLM(hf_cfg)
    hf.save_pretrained(tmp_path)

    config, params = load_hf_bert(str(tmp_path), dtype=jnp.float32,
                                  remat=False)
    model = BertModel(config)
    ids = np.random.RandomState(5).randint(0, 128, size=(2, 10))
    ours = model.forward(params, jnp.asarray(ids))
    hf.eval()
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs,
                               rtol=2e-4, atol=2e-4)
