"""The ragged paged LATENT attention kernel
(``ops/ragged_mla_attention.py``, Pallas under the generic interpreter
here) against its XLA formulation: mixed rows of one dispatch (two chunks
of one prompt, a decode row, a chunk over cached rows, idle rows), the
page write-back, and a table far wider than any context. Outputs to 2e-6
(both f32, the block-wise softmax orders its sums differently); pool
bytes bitwise."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ragged_mla_attention as M

H, W, VW, PAGE, P, QB = 4, 256, 128, 8, 40, 8
USED = 200          # lanes of a row in use; the rest is zero padding


def _dispatch(width, rows, tables, r_cap=6, t_cap=32, seed=1):
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(P, PAGE, W)).astype(np.float32)
    pool[..., USED:] = 0
    bt = np.full((r_cap, width), P - 1, np.int32)
    kv, qs, ql, ws, wf, we = (np.zeros(r_cap, np.int32) for _ in range(6))
    first, last, t = {}, {}, 0
    for i, (s, start, n) in enumerate(rows):
        bt[i, :len(tables[s])] = tables[s]
        kv[i], qs[i], ql[i] = start + n, start, n
        first.setdefault(s, (start, t))
        last[s] = start + n
        t += n
    for i, (s, _, _) in enumerate(rows):
        ws[i], wf[i] = first[s]
        we[i] = last[s]
    q = rng.normal(size=(t_cap, H, W)).astype(np.float32)
    new = rng.normal(size=(t_cap, W)).astype(np.float32)
    q[..., USED:] = 0
    new[:, USED:] = 0
    return [jnp.asarray(a) for a in (q, new, pool, bt, kv, qs, ql, ws, wf,
                                     we)]


ROWS = [("A", 0, 8), ("A", 8, 5), ("B", 20, 1), ("C", 11, 8)]
TABLES = {"A": [1, 2, 3], "B": [4, 5, 6], "C": [7, 8, 9]}


def _both(args, qb=QB):
    ok, pk = M.ragged_mla_attention(*args, VW, 0.1, qb)
    ox, px = M.ragged_mla_attention_xla(*args, VW, 0.1, qb)
    return [np.asarray(a._data) for a in (ok, pk, ox, px)]


@pytest.mark.parametrize("width", [3, 12, 64])
def test_kernel_equals_xla_on_mixed_rows(width):
    ok, pk, ox, px = _both(_dispatch(width, ROWS, TABLES))
    for i, (_, _, n) in enumerate(ROWS):
        assert np.abs(ok[i, :n] - ox[i, :n]).max() < 2e-6
    assert not ok[len(ROWS):].any()             # idle rows give zeros
    assert (pk[:P - 1] == px[:P - 1]).all()     # the pages, bitwise


def test_output_does_not_depend_on_the_table_width():
    narrow = _both(_dispatch(3, ROWS, TABLES))[0]
    wide = _both(_dispatch(64, ROWS, TABLES))[0]
    assert (narrow == wide).all()


def test_new_rows_land_in_their_pages():
    args = _dispatch(12, ROWS, TABLES)
    pool = _both(args)[1]
    new = np.asarray(args[1])
    # sequence A wrote positions 0..12 from packed tokens 0..12
    for pos in range(13):
        assert (pool[TABLES["A"][pos // PAGE], pos % PAGE]
                == new[pos]).all()
    # B's decode token (packed index 13) at position 20
    assert (pool[TABLES["B"][20 // PAGE], 20 % PAGE] == new[13]).all()
    # what C already held is untouched
    assert (pool[7] == np.asarray(args[2])[7]).all()


def test_decode_shape_and_long_contexts():
    """The decode-only program (one query a row) over contexts of
    several walk blocks (a block is 256 tokens)."""
    rows = [("A", 299, 1), ("B", 4, 1)]
    tables = {"A": list(range(38)), "B": [38]}
    args = _dispatch(40, rows, tables, r_cap=4, t_cap=4, seed=3)
    ok, pk, ox, px = _both(args, qb=1)
    assert np.abs(ok[:2] - ox[:2]).max() < 2e-6
    assert (pk[:P - 1] == px[:P - 1]).all()


def test_supported_and_row_width():
    assert M.latent_row_width(512, 64) == 640
    assert M.latent_row_width(32, 8) == 128
    q = jnp.zeros((8, H, W))
    assert M.supported(q, jnp.zeros((8, W)), jnp.zeros((P, PAGE, W)), VW, 4)
    assert not M.supported(q, jnp.zeros((8, W)),
                           jnp.zeros((P, PAGE, W - 128)), VW, 4)
