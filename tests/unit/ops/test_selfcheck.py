"""The kernel self-check (``ops/pallas/selfcheck.py``) has teeth.

``chip_smoke.py`` runs it compiled at Mistral-7B shapes on the chip; here
the same checks run through the Pallas interpreter at a tiny size (the
whole set runs in ``tests/unit/test_chip_smoke.py``).  What is provable
anywhere: a kernel that is wrong, or returns a NaN, fails the gate.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import LlamaConfig
from deepspeed_tpu.ops.pallas import lattice, selfcheck


@pytest.fixture
def shapes(monkeypatch):
    cfg = LlamaConfig.tiny(dtype=jnp.float32, sliding_window=96)
    # residency bound at the tiny context, so that twice the context streams
    monkeypatch.setattr(lattice, "RESIDENT_VMEM_ELEMS",
                        cfg.max_seq_len * cfg.hd)
    return selfcheck.KernelShapes.for_model(cfg)


def test_shapes_follow_the_model(shapes):
    assert (shapes.heads, shapes.kv_heads, shapes.head_dim) == (8, 4, 16)
    assert shapes.window == 96
    assert lattice.resident_fits(shapes.seq, shapes.head_dim)
    assert not lattice.resident_fits(shapes.stream_seq, shapes.head_dim)


def test_selfcheck_detects_broken_kernel(monkeypatch, shapes):
    """A kernel producing wrong values (the VMEM-overflow class) must
    fail the gate."""
    fa_mod = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")
    real = fa_mod.flash_attention

    def broken(q, k, v, *a, **kw):
        return real(q, k, v, *a, **kw) * 1.5  # silently wrong scale

    monkeypatch.setattr(fa_mod, "flash_attention", broken)
    monkeypatch.setattr(selfcheck, "CHECKS", (selfcheck.check_flash,))
    with pytest.raises(AssertionError, match="selfcheck FAILED.*flash"):
        selfcheck.run_checks(shapes, interpret=True)


def test_selfcheck_detects_nan(monkeypatch, shapes):
    da_mod = importlib.import_module(
        "deepspeed_tpu.ops.pallas.decode_attention")
    real = da_mod.decode_attention

    def nan_kernel(q, k_cache, v_cache, lengths, **kw):
        out = real(q, k_cache, v_cache, lengths, **kw)
        return out.at[0].set(np.nan)

    monkeypatch.setattr(da_mod, "decode_attention", nan_kernel)
    monkeypatch.setattr(selfcheck, "CHECKS", (selfcheck.check_decode,))
    with pytest.raises(AssertionError, match="selfcheck FAILED.*decode"):
        selfcheck.run_checks(shapes, interpret=True)


def test_streamed_check_refuses_a_resident_shape(shapes):
    """The streamed check must not quietly re-run the resident kernels."""
    import dataclasses

    resident = dataclasses.replace(shapes, stream_seq=shapes.seq)
    with pytest.raises(ValueError, match="RESIDENT_VMEM_ELEMS"):
        selfcheck.check_flash_streamed(resident, interpret=True)


def test_the_latent_paged_check_has_teeth(monkeypatch, shapes):
    """The paged case over a latent cache (128 heads on one row of 576 in
    five planes, V its leading 512; a token a row, and a chunk's several
    tokens a row) passes, and a kernel that takes V from the wrong numbers
    of the row fails it."""
    pa = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")
    monkeypatch.setattr(selfcheck, "CHECKS", (selfcheck.check_paged_latent,))
    check, chunk = selfcheck.run_checks(shapes, interpret=True)
    assert "latent" in check.name and check.ok
    assert "tokens a row" in chunk.name and chunk.ok
    real = pa.paged_decode_attention

    def shifted(q, k_pool, *a, **kw):
        return real(q, jnp.roll(k_pool, 1, axis=-1), *a, **kw)

    monkeypatch.setattr(pa, "paged_decode_attention", shifted)
    with pytest.raises(AssertionError, match="selfcheck FAILED.*latent"):
        selfcheck.run_checks(shapes, interpret=True)


def test_the_hybrid_paged_check_has_teeth(monkeypatch, shapes):
    """The paged case at K 192 / V 128 runs both kinds of layer, and a
    kernel that loses the sink fails it."""
    pa = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")
    monkeypatch.setattr(selfcheck, "CHECKS", (selfcheck.check_paged_hybrid,))
    names = [c.name for c in selfcheck.run_checks(shapes, interpret=True)]
    assert len(names) == 2 and "kv_heads=4" in names[0] \
        and "sink=True" in names[1]
    real = pa.paged_decode_attention
    monkeypatch.setattr(
        pa, "paged_decode_attention",
        lambda *a, sink=None, **kw: real(*a, sink=None, **kw))
    with pytest.raises(AssertionError, match="selfcheck FAILED.*sink=True"):
        selfcheck.run_checks(shapes, interpret=True)


def test_the_share_check_leaves_most_tiles_empty(monkeypatch, shapes):
    monkeypatch.setattr(selfcheck, "CHECKS", (selfcheck.check_moe_share,))
    (check,) = selfcheck.run_checks(shapes, interpret=True)
    used, tiles = map(int, __import__("re").search(
        r"(\d+) of (\d+) tiles", check.name).groups())
    assert 1 <= used <= tiles // 2


@pytest.mark.parametrize("check", ["check_moe_latent",
                                   "check_ssm_state_update_lanes"])
def test_the_kernels_second_forms_are_checked_too(monkeypatch, shapes, check):
    """An expert of two matrices at a latent's width, and a state held two
    heads a lane row (PR 52): each passes in the interpreter and names the
    form in what it reports."""
    monkeypatch.setattr(selfcheck, "CHECKS", (getattr(selfcheck, check),))
    names = [c.name for c in selfcheck.run_checks(shapes, interpret=True)]
    assert names and all(("relu2" in n) or ("lanes" in n) for n in names)


def test_the_delta_rules_update_is_checked_at_a_published_layers_widths(
        monkeypatch, shapes):
    """``delta_state_update`` (PR 57) at 64 heads of 128 x 128: the pool
    after and ``o``, in the interpreter, and a kernel that forgets the
    decay fails it."""
    dsu = importlib.import_module(
        "deepspeed_tpu.ops.pallas.delta_state_update")
    monkeypatch.setattr(selfcheck, "CHECKS",
                        (selfcheck.check_delta_state_update,))
    names = [c.name for c in selfcheck.run_checks(shapes, interpret=True)]
    assert names == ["delta_state_update_state", "delta_state_update_o"]
    real = dsu.delta_state_update
    monkeypatch.setattr(
        dsu, "delta_state_update",
        lambda pool, layer, first, a, *rest, **kw: real(
            pool, layer, first, a * 0 + 1, *rest, **kw))
    with pytest.raises(AssertionError, match="selfcheck FAILED.*delta_state"):
        selfcheck.run_checks(shapes, interpret=True)


def test_the_convs_step_is_checked_at_the_widest_published_conv(
        monkeypatch, shapes):
    """``conv_tail_update`` (PR 58) at three streams of 8,192 channels and
    four taps: the pool after (copies: exact) and the conv's output; and
    (PR 60) with no taps at Nemotron-H's mixer's channels: the pool after
    and the tails handed back as they lay, both exact; in the interpreter,
    and a kernel that moves a dead row's tail fails it."""
    ctu = importlib.import_module(
        "deepspeed_tpu.ops.pallas.conv_tail_update")
    monkeypatch.setattr(selfcheck, "CHECKS",
                        (selfcheck.check_conv_tail_update,))
    names = [c.name for c in selfcheck.run_checks(shapes, interpret=True)]
    assert names == ["conv_tail_update_pool", "conv_tail_update_out",
                     "conv_tail_update_tails_pool",
                     "conv_tail_update_tails_out"]
    real = ctu.conv_tail_update
    monkeypatch.setattr(
        ctu, "conv_tail_update",
        lambda pool, layer, first, x, w, b, valid, **kw: real(
            pool, layer, first, x, w, b, valid * 0 + 1, **kw))
    with pytest.raises(AssertionError, match="selfcheck FAILED.*conv_tail"):
        selfcheck.run_checks(shapes, interpret=True)
