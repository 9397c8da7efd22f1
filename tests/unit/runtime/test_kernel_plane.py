"""Engine integration of the Pallas kernel plane (ISSUE 12).

kernels.fused_adam: the two-pass fused step must reproduce the optax
chain's training trajectory exactly (the whole point of the bit-parity
kernel); kernels.overlap_collectives: the chunked-ring stage-3 branch
must reproduce plain GSPMD stage 3.  Plus the memory-ledger attribution
for kernel scratch and the config-gating fallbacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel import MeshLayout
from deepspeed_tpu.utils import groups


@pytest.fixture(autouse=True)
def _hub_left_as_found():
    """Engines built here with ``telemetry.enabled`` switch the process's
    hub on; left on, it counts into whatever file this worker runs next."""
    yield
    from deepspeed_tpu.telemetry import get_telemetry

    get_telemetry().reset()


def make_engine(extra=None, zero=2, clip=1.0, opt="Adam", dp=8,
                opt_params=None, attn="xla"):
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(dp, dp=dp))
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32, remat=False,
                           attn_impl=attn)
    model = LlamaModel(cfg, mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    conf = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": opt,
                      "params": dict(opt_params or {"lr": 1e-3})},
        # persistence threshold 0: tiny-model leaves must actually shard
        # at stage 3 or the overlap ring would be a silent no-op (the
        # census test below exists to catch exactly that)
        "zero_optimization": {"stage": zero,
                              "stage3_param_persistence_threshold": 0},
        "gradient_clipping": clip,
    }
    if extra:
        conf.update(extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=conf, mesh=mesh)
    return engine


def batch(rows=16, seq=32, seed=0):
    ids = np.random.RandomState(seed).randint(0, 512, size=(rows, seq))
    return {"input_ids": jnp.asarray(ids)}


def run(engine, b, steps=4):
    return [float(engine.train_step(b)["loss"]) for _ in range(steps)]


def test_fused_adam_matches_optax_chain_with_clipping():
    b = batch()
    base = make_engine()
    losses_b = run(base, b)
    gn_b = base.get_global_grad_norm()

    fused = make_engine({"kernels": {"fused_adam": True}})
    assert fused.fused_adam_enabled
    losses_f = run(fused, b)
    gn_f = fused.get_global_grad_norm()

    np.testing.assert_allclose(losses_b, losses_f, rtol=1e-5)
    np.testing.assert_allclose(gn_b, gn_f, rtol=1e-4)
    # optax state layout preserved: count marched with the steps
    from deepspeed_tpu.ops.pallas.fused_optimizer import find_adam_state

    _, adam = find_adam_state(fused.state.opt_state)
    assert int(adam.count) == 4


def test_fused_adam_adamw_weight_decay_matches():
    b = batch(seed=1)
    kw = {"opt": "AdamW", "opt_params": {"lr": 1e-3,
                                         "weight_decay": 0.01}}
    base = make_engine(**kw)
    fused = make_engine({"kernels": {"fused_adam": True}}, **kw)
    assert fused.fused_adam_enabled
    assert fused._fused_adam_cfg.decoupled_wd
    np.testing.assert_allclose(run(base, b), run(fused, b), rtol=1e-5)


def test_fused_adam_gates_off_for_non_adam_and_logs():
    eng = make_engine({"kernels": {"fused_adam": True}}, opt="SGD",
                      clip=0.0)
    assert not eng.fused_adam_enabled  # optax chain kept, no crash
    losses = run(eng, batch(), steps=2)
    assert losses[1] < losses[0]


def test_overlap_zero3_matches_gspmd_stage3():
    b = batch(seed=2)
    base = make_engine(zero=3, clip=0.0)
    losses_b = run(base, b)
    ov = make_engine({"kernels": {"overlap_collectives": True,
                                  "overlap_chunks": 2}}, zero=3, clip=0.0)
    assert ov.overlap_zero3
    losses_o = run(ov, b)
    np.testing.assert_allclose(losses_b, losses_o, rtol=2e-4)


def test_overlap_with_fused_adam_compose():
    b = batch(seed=3)
    base = make_engine(zero=3)
    both = make_engine({"kernels": {"overlap_collectives": True,
                                    "overlap_chunks": 2,
                                    "fused_adam": True}}, zero=3)
    assert both.overlap_zero3 and both.fused_adam_enabled
    np.testing.assert_allclose(run(base, b), run(both, b), rtol=2e-4)


def test_overlap_ring_rides_the_comm_verbs():
    """The stage-3 overlap branch's ring hops must land in the
    CollectiveLedger census (the dslint/ledger contract for every new
    collective path)."""
    from deepspeed_tpu.comm.comm import comms_logger
    from deepspeed_tpu.telemetry.collective_ledger import CollectiveLedger

    led = CollectiveLedger(max_entries=4096, tail=256, enabled=True)
    old = comms_logger.ledger
    comms_logger.ledger = led
    try:
        eng = make_engine({"kernels": {"overlap_collectives": True,
                                       "overlap_chunks": 2}}, zero=3,
                          clip=0.0)
        run(eng, batch(), steps=1)
    finally:
        comms_logger.ledger = old
    ops = [e["op"] for e in led.snapshot().get("tail", [])]
    assert "ppermute" in ops


def test_kernel_scratch_registers_in_memory_ledger(monkeypatch):
    import importlib

    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    # the flash entry follows the route the op would take for the model's
    # shape (``LlamaModel.uses_flash_kernels``): say "kernel", as on a TPU
    monkeypatch.setattr(
        importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention"),
        "flash_route", lambda *a, **k: ("kernel", None))

    eng = make_engine({"kernels": {"overlap_collectives": True,
                                   "overlap_chunks": 2},
                       "telemetry": {"enabled": True, "jsonl": False,
                                     "prometheus": False}},
                      zero=3, clip=0.0, attn="flash")
    led = eng.memory_ledger or get_memory_ledger()
    keys = [e["key"] for e in led.entries()
            if e["pool"] == "collective_scratch"]
    assert "engine/overlap_ring_staging" in keys
    # flash scratch keys on the MODEL route (attn_impl and the op's own
    # choice), not the config knob — the knob without routing would
    # attribute bytes that don't exist
    assert "engine/flash_softmax_stats" in keys
    get_memory_ledger().reset()  # process-global: scrub the prior
    # engine's entries so the xla build is judged on its own
    xla_eng = make_engine({"kernels": {"flash_attention": True},
                           "telemetry": {"enabled": True, "jsonl": False,
                                         "prometheus": False}},
                          zero=3, clip=0.0, attn="xla")
    xla_keys = [e["key"] for e in (xla_eng.memory_ledger
                                   or get_memory_ledger()).entries()
                if e["pool"] == "collective_scratch"]
    assert "engine/flash_softmax_stats" not in xla_keys


@pytest.mark.parametrize("family, route, listed", [
    ("bert", "reference", False), ("bert", "kernel", True),
    ("llama_flash", "reference", False), ("llama_flash", "kernel", True),
    ("llama_xla", "kernel", False)])
def test_flash_scratch_is_keyed_on_the_route_the_module_reports(
        family, route, listed, monkeypatch):
    """ONE rule for the ledger's ``engine/flash_softmax_stats`` entry: the
    module says whether its step holds the flash kernels
    (``uses_flash_kernels``), from whether its attention calls the flash
    op (BERT: always, whatever ``attn_impl`` says; Llama: the option) and
    from the op's own choice for the model's shape (``flash_route``):
    absent off the TPU, where the reference runs; present where the
    kernels would.  The engine imports no model class for it."""
    import importlib

    from deepspeed_tpu.models.bert import BertConfig, BertModel
    from deepspeed_tpu.telemetry.memory import get_memory_ledger

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    assert fa.flash_route(128, 16)[0] == "reference"   # the CPU here
    monkeypatch.setattr(fa, "flash_route", lambda *a, **k: (route, None))
    get_memory_ledger().reset()
    groups.reset_mesh()
    mesh = groups.initialize_mesh(MeshLayout.infer(8, dp=8))
    if family == "bert":
        model = BertModel(BertConfig.tiny(attn_impl="xla"), mesh=mesh)
    else:
        model = LlamaModel(LlamaConfig.tiny(
            attn_impl=family.split("_")[1]), mesh=mesh)
    assert model.uses_flash_kernels() == listed
    eng, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "telemetry": {"enabled": True, "jsonl": False,
                              "prometheus": False}}, mesh=mesh)
    keys = [e["key"] for e in (eng.memory_ledger
                               or get_memory_ledger()).entries()]
    assert ("engine/flash_softmax_stats" in keys) == listed
    get_memory_ledger().reset()


def test_engine_imports_no_model_class():
    """The shared engine asks a module what it does; it tests no model's
    identity (a lower layer does not know a higher one)."""
    import ast
    import inspect

    from deepspeed_tpu.runtime import engine

    tree = ast.parse(inspect.getsource(engine))
    imported = [(node.module or "") for node in ast.walk(tree)
                if isinstance(node, ast.ImportFrom)]
    assert not [m for m in imported if "models" in m.split(".")], imported


def test_fused_adam_engine_checkpoint_state_interchanges():
    """A fused engine's opt_state must load back into a non-fused engine
    shape-for-shape (same optax layout)."""
    fused = make_engine({"kernels": {"fused_adam": True}})
    run(fused, batch(), steps=2)
    base = make_engine()
    flat_f = jax.tree.leaves(fused.state.opt_state)
    flat_b = jax.tree.leaves(base.state.opt_state)
    assert len(flat_f) == len(flat_b)
    for a, c in zip(flat_f, flat_b):
        assert np.shape(a) == np.shape(c)
