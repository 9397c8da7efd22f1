"""``ops/pallas/conv_tail_update``: a decode step of a depthwise causal conv
over the rows' held tails, in place in the tails' pool.  The kernel in the
Pallas interpreter against the ``jax.numpy`` reference (which is
``models/mamba2.conv`` at one token, term for term), at the three families'
arguments cut small, emitting the conv's output or, for a caller that hands
no taps, the rows' tails as they lay (Nemotron-H's mixers' way since PR 60);
what a dead row, a fresh slot and a stretch of slots that starts inside a
tile of rows must come to; and the rule that says where the chip holds the
pool row-major."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import delta_rule, mamba2
from deepspeed_tpu.ops.pallas import conv_tail_update as ctu

F32 = jnp.float32
#: (taps, channels, bias): Solar-Open-2's three streams with no bias,
#: Falcon-H1's and Nemotron-H's ``[xs | B | C]`` with one, cut small; and
#: channels that are several stretches of whole lane tiles
FAMILIES = {"solar-open2": (4, 3 * 64, False),
            "falcon-h1": (4, 32 + 2 * 32, True),
            "nemotron-h": (4, 256 + 2 * 64, True),
            "three-taps": (3, 1280, True)}


def _operands(seed, layers, slots, rows, taps, channels, bias, dtype):
    rng = np.random.RandomState(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    return (normal(layers, slots, (taps - 1) * channels).astype(dtype),
            normal(rows, channels).astype(dtype),
            0.5 * normal(taps, channels),
            normal(channels) if bias else None)


#: what the second result is: the conv's output, or (no taps handed) the
#: rows' tails as they lay
EMITS = ["conv", "tails"]


def _taps(emits, w, b):
    return (w, b) if emits == "conv" else (None, None)


@pytest.mark.parametrize("emits", EMITS)
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_kernel_is_the_reference_at_each_familys_arguments(family, dtype,
                                                               emits):
    taps, channels, bias = FAMILIES[family]
    pool, x, w, b = _operands(0, 3, 12, 11, taps, channels, bias, dtype)
    w, b = _taps(emits, w, b)
    valid = jnp.asarray([1, 1, 0, 1, 1, 1, 0, 1, 1, 1, 1])
    want = ctu.conv_tail_update_reference(pool, 1, 1, x, w, b, valid)
    got = ctu.conv_tail_update(pool, 1, 1, x, w, b, valid, interpret=True)
    # the tails are copies: exact in either type
    np.testing.assert_array_equal(got[0], want[0])
    assert got[1].dtype == want[1].dtype == dtype
    if emits == "tails":
        # every row's, live or dead, as it lay before the step: to the bit
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[1], pool[1, 1:12])
    # the kernel sums in float32 and rounds once; the reference rounds
    # every product and sum to the rows' type
    tol = 1e-5 if dtype == F32 else 4e-2
    np.testing.assert_allclose(got[1].astype(F32), want[1].astype(F32),
                               rtol=tol, atol=tol)
    # and the other layers, and the slots that are no row's, as they lay
    for other in (0, 2):
        np.testing.assert_array_equal(got[0][other], pool[other])
    np.testing.assert_array_equal(got[0][1, 0], pool[1, 0])


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpret"])
def test_the_reference_is_the_models_conv_at_one_token(interpret):
    """Term for term: ``mamba2.conv`` (the chunk path's, and every decode
    step's before PR 58) at ``tokens = 1`` gives the bits the reference
    gives, output and outgoing tail; the kernel the same tail."""
    dims = mamba2.Mamba2Dims(heads=4, d_head=16, d_state=16, groups=2,
                             d_conv=4)
    rng = np.random.RandomState(1)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    m = {"conv_w": normal(4, dims.conv_dim), "conv_b": normal(dims.conv_dim),
         "dt_bias": normal(dims.heads), "A_log": normal(dims.heads)}
    R, dt = 5, jnp.bfloat16
    p = normal(R, dims.proj_dim).astype(dt)
    tail = normal(R, 3 * dims.conv_dim).astype(dt)
    valid = jnp.asarray([1, 0, 1, 1, 0])
    xs, B, C, _, _, left = mamba2.conv(dims, m, p, tail, 1, valid, dt)
    pool, out = ctu.conv_tail_update(
        tail[None], 0, 0, p[:, dims.d_ssm:dims.d_ssm + dims.conv_dim],
        m["conv_w"], m["conv_b"], valid, interpret=interpret)
    np.testing.assert_array_equal(pool[0], left)
    if interpret is None:
        np.testing.assert_array_equal(
            out, jnp.concatenate([xs.reshape(R, -1), B.reshape(R, -1),
                                  C.reshape(R, -1)], axis=1))


@pytest.mark.parametrize("emits", EMITS)
@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpret"])
def test_a_row_that_is_no_sequences_leaves_its_tail_as_it_lay(interpret,
                                                              emits):
    """A dead decode row on a prefilling request's slot must not disturb
    what the chunk wrote (``engine_v2._beside``'s contract)."""
    pool, x, w, b = _operands(2, 2, 21, 19, 4, 128, True, jnp.bfloat16)
    w, b = _taps(emits, w, b)
    valid = jnp.asarray(np.arange(19) % 3 != 1, jnp.int32)
    after, out = ctu.conv_tail_update(pool, 0, 2, x, w, b, valid,
                                      interpret=interpret)
    if emits == "tails":
        np.testing.assert_array_equal(out, pool[0, 2:])
    before, after = np.asarray(pool[0, 2:], F32), np.asarray(after[0, 2:],
                                                             F32)
    dead = np.asarray(valid) == 0
    np.testing.assert_array_equal(after[dead], before[dead])
    # a live row's: shifted by one token, the step's input last
    np.testing.assert_array_equal(after[~dead, :2 * 128],
                                  before[~dead, 128:])
    np.testing.assert_array_equal(after[~dead, 2 * 128:],
                                  np.asarray(x, F32)[~dead])


@pytest.mark.parametrize("emits", EMITS)
@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpret"])
def test_a_fresh_slots_zeros_leave_the_last_tap_alone(interpret, emits):
    """A sequence's first token: zeros before it, so the conv is its last
    tap's product (and the bias), and the tails handed back are zeros."""
    _, x, w, b = _operands(3, 1, 1, 9, 4, 256, True, F32)
    pool = jnp.zeros((2, 10, 3 * 256), F32)
    after, out = ctu.conv_tail_update(pool, 1, 1, x, *_taps(emits, w, b),
                                      jnp.ones((9,), jnp.int32),
                                      interpret=interpret)
    if emits == "conv":
        np.testing.assert_allclose(out, jax.nn.silu(w[3] * x + b),
                                   rtol=1e-6, atol=1e-6)
    else:
        assert out.shape == (9, 3 * 256) and not bool(out.any())
    assert not bool(after[1, 1:, :2 * 256].any())
    np.testing.assert_array_equal(after[1, 1:, 2 * 256:], x)


@pytest.mark.parametrize("first, rows, slots", [
    (1, 16, 17), (1, 8, 9), (3, 9, 14), (0, 8, 8), (5, 2, 24), (7, 17, 24),
    (1, 3, 4)])
@pytest.mark.parametrize("emits", EMITS)
def test_rows_whose_slots_start_inside_a_tile_of_rows(first, rows, slots,
                                                      emits):
    """The kernel walks the layer's slots from 0 in tiles of eight, the
    call's rows laid at their slots' places: whichever stretch they hold,
    they get their own tails, and no other slot is touched."""
    pool, x, w, b = _operands(first, 2, slots, rows, 4, 128, False,
                              jnp.bfloat16)
    w, b = _taps(emits, w, b)
    valid = jnp.ones((rows,), jnp.int32)
    want = ctu.conv_tail_update_reference(pool, 1, first, x, w, b, valid)
    got = ctu.conv_tail_update(pool, 1, first, x, w, b, valid,
                               interpret=True)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1].astype(F32), want[1].astype(F32),
                               rtol=4e-2, atol=4e-2)
    untouched = np.r_[0:first, first + rows:slots]
    np.testing.assert_array_equal(got[0][1, untouched], pool[1, untouched])


def test_a_traced_layer_and_first_slot_are_operands_not_shapes():
    pool, x, w, b = _operands(4, 3, 12, 6, 4, 128, True, F32)
    valid = jnp.ones((6,), jnp.int32)
    step = jax.jit(lambda pool, layer, first: ctu.conv_tail_update(
        pool, layer, first, x, w, b, valid, interpret=True))
    for layer, first in ((0, 1), (2, 5)):
        want = ctu.conv_tail_update_reference(pool, layer, first, x, w, b,
                                              valid)
        got = step(pool, layer, first)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layers, slots, itemsize, stays", [
    (6, 97, 2, True), (5, 129, 2, True), (3, 193, 2, True),    # the cells'
    (72, 96, 2, True), (36, 193, 2, True), (3, 33, 2, True),
    (72, 97, 2, False), (2, 129, 2, False), (3, 17, 2, False),
    (8, 97, 4, False), (1, 5, 2, True)])
def test_the_rule_says_where_the_chip_holds_the_pool_row_major(
        layers, slots, itemsize, stays):
    """``tests/unit/ops/test_tpu_compile_state.py`` holds the rule to the
    compiler; here, what it says of the shapes the records name."""
    assert ctu.rows_on_sublanes(layers, slots, itemsize) is stays


@pytest.mark.parametrize("interpret, route", [(None, "reference"),
                                              (True, "interpret")])
def test_the_route_is_counted_under_the_ops_name(interpret, route):
    from deepspeed_tpu import telemetry

    tel = telemetry.get_telemetry()
    tel.reset()
    tel.configure(enabled=True, jsonl=False, prometheus=False)
    try:
        pool, x, w, b = _operands(5, 1, 4, 3, 4, 128, False, F32)
        ctu.conv_tail_update(pool, 0, 1, x, w, b, jnp.ones((3,), jnp.int32),
                             interpret=interpret)
        counters = {m.name: m.value for m in tel.registry.metrics().values()
                    if m.name.startswith("ops/conv_tail_update/")}
        assert counters == {f"ops/conv_tail_update/{route}_calls": 1.0}
    finally:
        tel.reset()


@pytest.mark.parametrize("family", ["mamba2", "mamba2-moves-only", "delta"])
def test_a_familys_decode_step_through_the_kernel_is_its_reference(
        family, monkeypatch):
    """``mamba2.decode`` / ``delta_rule.decode`` with the conv's kernel in
    the interpreter against the same step on the reference: the two arrays
    going out and the step's output.  The Mamba-2 step as Falcon-H1 runs it
    and the delta rule hand their taps and take the conv's output; as
    Nemotron-H runs it (``kernel_conv`` False, PR 60) it hands the kernel
    none, takes the tails as they lay and leaves the conv to
    ``mamba2.conv``, the values way's arithmetic."""
    rng = np.random.RandomState(6)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    R, valid = 5, jnp.asarray([1, 1, 0, 1, 1])
    if family != "delta":
        module = mamba2
        dims = mamba2.Mamba2Dims(heads=4, d_head=32, d_state=16, groups=2,
                                 d_conv=4)
        m = {"conv_w": normal(4, dims.conv_dim),
             "conv_b": normal(dims.conv_dim), "dt_bias": normal(dims.heads),
             "A_log": normal(dims.heads), "D": normal(dims.heads)}
        p = normal(R, dims.proj_dim)
    else:
        module = delta_rule
        dims = delta_rule.DeltaDims(heads=2, d_head=64, d_conv=4)
        m = {"conv_w": normal(4, 3 * dims.width),
             "dt_bias": normal(dims.width), "A_log": normal(dims.heads)}
        p = {"qkv": normal(R, 3 * dims.width), "f": normal(R, dims.width),
             "beta": normal(R, dims.heads)}
    held = {name: (normal(2, R + 1, *shape).astype(dt), 1, 1)
            for name, shape, dt in dims.state_parts(F32)}
    if family != "delta":
        decode = lambda: module.decode(
            dims, m, p, {}, held, valid, F32,
            kernel_conv=family == "mamba2")[::2]
    else:
        decode = lambda: module.decode(dims, m, p, held, valid)
    want_y, want = decode()
    real, tapped = ctu.conv_tail_update, []

    def through_the_kernel(*a):
        tapped.append(a[4] is not None)
        return real(*a, interpret=True)

    monkeypatch.setattr(module, "conv_tail_update", through_the_kernel)
    got_y, got = decode()
    assert tapped == [family != "mamba2-moves-only"]
    np.testing.assert_allclose(got_y, want_y, rtol=1e-4, atol=1e-5)
    assert sorted(got) == sorted(want) == sorted(held)
    np.testing.assert_array_equal(got["conv"], want["conv"])
    for name in got:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("kernel_conv", [True, False],
                         ids=["conv-in-the-op", "op-moves-only"])
def test_the_mixers_decode_takes_the_tail_as_a_value_or_where_it_lies(
        kernel_conv):
    """``mamba2.decode`` keeps the values way in (no adapter hands it a
    tail as a value since PR 60: ROADMAP D16): the tail handed over as a
    value and the tail moved in a pool of one layer, with the conv in the
    op (Falcon-H1's way) or the op handing the tails back as they lay for
    the same ``mamba2.conv`` (Nemotron-H's), give the same step, to the bit
    on the reference."""
    rng = np.random.RandomState(7)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    dims = mamba2.Mamba2Dims(heads=4, d_head=32, d_state=16, groups=2,
                             d_conv=4)
    m = {"conv_w": normal(4, dims.conv_dim), "conv_b": normal(dims.conv_dim),
         "dt_bias": normal(dims.heads), "A_log": normal(dims.heads),
         "D": normal(dims.heads)}
    R, dt, valid = 5, jnp.bfloat16, jnp.asarray([1, 0, 1, 1, 1])
    p = normal(R, dims.proj_dim).astype(dt)
    state = {name: normal(R, *shape).astype(t)
             for name, shape, t in dims.state_parts(dt)}
    pools = {name: (part[None], 0, 0) for name, part in state.items()}
    y_a, values, arrays_a = mamba2.decode(
        dims, m, p, {"conv": state["conv"]}, {"ssm": pools["ssm"]}, valid, dt)
    y_b, none, arrays_b = mamba2.decode(dims, m, p, {}, pools, valid, dt,
                                        kernel_conv)
    assert sorted(values) == ["conv"] and none == {}
    assert sorted(arrays_a) == ["ssm"] and sorted(arrays_b) == ["conv", "ssm"]
    np.testing.assert_array_equal(y_a, y_b)
    np.testing.assert_array_equal(values["conv"], arrays_b["conv"][0])
    np.testing.assert_array_equal(arrays_a["ssm"], arrays_b["ssm"])
