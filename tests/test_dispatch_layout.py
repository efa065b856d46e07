"""One dispatch's host-built metadata rides to the device as ONE flat
int32 buffer (ISSUE 31): `DispatchLayout` says where each of the 18
fields of ``_mixed_forward``'s argument list lies in it, and since
ISSUE 38 ``prev_idx`` (per packed token: -1, or where the token lies in
the last dispatch's output on the device). What the host
writes into the views comes back from the device-side unpack with its
shape, its dtype and, for the float fields, its bits."""

import jax
import numpy as np
import pytest

from paddle_tpu.inference.layer_step import DispatchLayout

FIELDS = ("tokens pos flat_idx last_idx tables "
          "kv_lens q_starts q_lens w_starts w_flats w_ends temps top_ps "
          "top_ks seeds slot_ids slot_vals cmodes prev_idx").split()
FLOATS = ("temps", "top_ps", "slot_vals")
SLOTS, TRASH = 8, 4096
# the serving cells' two program shapes (t_cap, r_cap, qb): a chunk
# budget of 128 tokens in 32 + 4 rows of 32, and the decode-only step
SHAPES = {"mixed": (128, 36, 32), "decode": (32, 32, 1)}
WIDTHS = (66, 161, 1057)


def _layout(shape, width):
    return DispatchLayout(*SHAPES[shape], width, SLOTS, TRASH)


def _want(lay):
    """Shape, dtype and fill value of every field, said once more here."""
    t, r, qb, w, b = lay.shape
    i32, f32 = np.int32, np.float32
    row = {k: ((r,), i32, 0) for k in (
        "last_idx kv_lens q_starts q_lens w_starts w_flats w_ends top_ks "
        "seeds cmodes").split()}
    return dict(row, tokens=((1, t), i32, 0), pos=((1, t), i32, 0),
                prev_idx=((1, t), i32, -1),
                flat_idx=((t,), i32, r * qb - 1),
                tables=((r, w), i32, TRASH), temps=((r,), f32, 0.0),
                top_ps=((r,), f32, 1.0), slot_ids=((r, b), i32, -1),
                slot_vals=((r, b), f32, 0.0))


def _random_fill(lay, rng, buf):
    """Random rows in every field of ``buf``; returns owned copies."""
    t, r, qb, w, b = lay.shape
    f = lay.views(buf)
    for name, (shape, dtype, _) in _want(lay).items():
        if name in FLOATS:
            # a spread over many binades, both signs, denormals and
            # the exact values the sampler meets most: no NaN
            v = rng.standard_normal(shape) * 10.0 ** rng.randint(
                -30, 30, shape)
            v.flat[::5] = (0.0, -0.0, 1.0, 1e-45, 0.7)[rng.randint(5)]
            f[name][...] = v.astype(np.float32)
        elif name == "slot_ids":
            f[name][...] = rng.randint(-1, 129280, shape)
        elif name == "tables":
            f[name][...] = rng.randint(0, TRASH + 1, shape)
        else:
            f[name][...] = rng.randint(0, 2 ** 31 - 1, shape)
    return {k: v.copy() for k, v in f.items()}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_offsets_do_not_overlap_and_cover_the_buffer(shape, width):
    lay = _layout(shape, width)
    assert [f[0] for f in lay.fields] == FIELDS
    at = 0
    for name, start, stop, shp, dtype in lay.fields:
        want_shape, want_dtype, _ = _want(lay)[name]
        assert start == at and stop == at + int(np.prod(shp)), name
        assert shp == want_shape and dtype == want_dtype, name
        at = stop
    assert at == lay.size and lay.nbytes == 4 * at
    t, r, qb, w, b = lay.shape
    assert lay.size == 4 * t + r * (w + 12 + 2 * b)
    # every word of the buffer belongs to exactly one view
    buf = lay.new()
    owner = np.zeros(lay.size, np.int32)
    base = buf.__array_interface__["data"][0]
    for v in lay.views(buf).values():
        assert v.base is not None and np.shares_memory(v, buf)
        lo = (v.__array_interface__["data"][0] - base) // 4
        owner[lo:lo + v.size] += 1
    assert (owner == 1).all()


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_host_pack_device_unpack_gives_every_field_back(shape, width):
    lay = _layout(shape, width)
    rng = np.random.RandomState(31 + width)
    buf = lay.new()
    wrote = _random_fill(lay, rng, buf)
    assert buf.dtype == np.int32 and buf.shape == (lay.size,)
    got = jax.jit(lay.unpack)(jax.numpy.asarray(buf))
    assert len(got) == len(FIELDS) == 19
    for name, a in zip(FIELDS, got):
        want_shape, want_dtype, _ = _want(lay)[name]
        a = np.asarray(a)
        assert a.shape == want_shape and a.dtype == want_dtype, name
        # the float fields bit for bit (-0.0 and denormals included)
        assert np.array_equal(a.view(np.int32),
                              wrote[name].view(np.int32)), name


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_new_buffer_reads_its_fill_values(shape):
    lay = _layout(shape, 161)
    got = jax.jit(lay.unpack)(jax.numpy.asarray(lay.new()))
    for name, a in zip(FIELDS, got):
        _, dtype, fill = _want(lay)[name]
        assert (np.asarray(a) == dtype(fill)).all(), name
    assert np.asarray(got[FIELDS.index("top_ps")]).view(np.int32)[0] \
        == np.float32(1.0).view(np.int32)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_few_rows_after_many_carry_nothing_over(shape):
    """Every dispatch takes a fresh buffer: a dispatch that fills three
    rows after one that filled them all reads the fill value in every
    slot it did not write, and neither the first dispatch's buffer nor
    its device array changes when the second is written."""
    lay = _layout(shape, 66)
    t, r, qb, w, b = lay.shape
    rng = np.random.RandomState(7)
    full = lay.new()
    wrote = _random_fill(lay, rng, full)
    on_device = jax.numpy.asarray(full)     # may alias `full` on the CPU
    few = lay.new()
    assert not np.shares_memory(few, full)
    f = lay.views(few)
    n = 3
    f["tokens"][0, :n] = 5
    f["tables"][:n, :2] = 9
    f["kv_lens"][:n] = 17
    f["temps"][:n] = 0.7
    f["slot_ids"][:n, 0] = 11
    got = dict(zip(FIELDS, jax.jit(lay.unpack)(jax.numpy.asarray(few))))
    want = _want(lay)
    assert (np.asarray(got["tokens"])[0, n:] == 0).all()
    assert (np.asarray(got["tables"])[n:] == TRASH).all()
    assert (np.asarray(got["tables"])[:n, 2:] == TRASH).all()
    assert (np.asarray(got["kv_lens"])[n:] == 0).all()
    assert (np.asarray(got["temps"])[n:] == 0).all()
    assert (np.asarray(got["slot_ids"])[n:] == -1).all()
    assert (np.asarray(got["slot_ids"])[:n, 1:] == -1).all()
    for name in set(FIELDS) - {"tokens", "tables", "kv_lens", "temps",
                               "slot_ids"}:
        _, dtype, fill = want[name]
        assert (np.asarray(got[name]) == dtype(fill)).all(), name
    # the earlier dispatch's host buffer and device array are untouched
    again = dict(zip(FIELDS, lay.unpack(on_device)))
    for name in FIELDS:
        assert np.array_equal(lay.views(full)[name].view(np.int32),
                              wrote[name].view(np.int32))
        assert np.array_equal(np.asarray(again[name]).view(np.int32),
                              wrote[name].view(np.int32))


def test_the_blank_cannot_be_written():
    lay = _layout("decode", 66)
    with pytest.raises(ValueError):
        lay._blank[0] = 1
    a, b = lay.new(), lay.new()
    assert a.flags.writeable and not np.shares_memory(a, b)
