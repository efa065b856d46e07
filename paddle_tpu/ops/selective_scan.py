"""The selective state-space scan over rows of a ragged batch.

A state-space (Mamba) mixer keeps, per sequence, a state ``h [N, C]``
(float32) and the last ``K - 1`` inputs of its causal depthwise
convolution. Over a row of ``Q`` tokens (a prefill chunk, a decode step
of one token, or a whole sequence) with per-token operands ``x, dt [C]``
and ``B, C [N]``:

    h_t[s, c] = exp(dt_t[c] A[s, c]) h_{t-1}[s, c] + dt_t[c] B_t[s] x_t[c]
    m_t[c]    = sum_s C_t[s] h_t[s, c] + D[c] x_t[c]

Rows are independent: each starts from the state it is handed (zeros at
a sequence's first token, else what the sequence's slot holds), stops at
its own length and hands its last state back, so a scan never crosses a
row boundary. The state and ``exp(dt A)`` are float32 whatever the
model's dtype.

Layouts: channels ride the lanes (``[.., N, C]``, ``[.., K - 1, C]``),
which is why the state is ``[N, C]`` here where the published code
writes ``[C, N]``.

Everything here is traceable ``jax.numpy`` under the scope
``paddle_tpu.ssm_scan``; the serving step and the model's plain forward
call the same two functions."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv_rows", "selective_scan_rows", "row_index"]

SCOPE = "paddle_tpu.ssm_scan"


def row_index(first, qblock, tokens):
    """``[R, QB]`` packed-token index of every row's ``QB`` slots:
    row ``r``'s tokens sit back to back from ``first[r]`` (slots past
    the row's length read a clipped neighbour; mask them)."""
    idx = first.astype(jnp.int32)[:, None] \
        + jnp.arange(qblock, dtype=jnp.int32)[None, :]
    return jnp.clip(idx, 0, tokens - 1)


def causal_conv_rows(x, prev, w, b, q_lens):
    """Causal depthwise convolution over each row and its new state.

    ``x [R, Q, C]`` the rows' inputs, ``prev [R, K-1, C]`` the ``K-1``
    inputs before each row (zeros at a sequence's start), ``w [C, K]``
    (``w[:, K-1]`` weighs the current input), ``b [C]``, ``q_lens [R]``.
    Returns ``(silu(conv + b) [R, Q, C]`` in ``x``'s dtype, the last
    ``K-1`` inputs up to each row's length ``[R, K-1, C]``)."""
    with jax.named_scope(SCOPE):
        k = w.shape[1]
        q = x.shape[1]
        seq = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
        wf = w.astype(jnp.float32)
        acc = b.astype(jnp.float32)[None, None, :]
        for j in range(k):
            acc = acc + seq[:, j:j + q, :].astype(jnp.float32) \
                * wf[None, None, :, j]
        # inputs q_len-(K-1) .. q_len-1 of the row sit at seq[q_len ..]
        at = q_lens.astype(jnp.int32)[:, None] \
            + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
        last = jnp.take_along_axis(seq, at[:, :, None], axis=1)
        return jax.nn.silu(acc).astype(x.dtype), last


def selective_scan_rows(x, dt, bmat, cmat, a, d, h0, q_lens,
                        long_rows=None):
    """The scan of the module docstring over each row.

    ``x [R, Q, C]`` (after the convolution), ``dt [R, Q, C]`` float32
    (after softplus), ``bmat``/``cmat [R, Q, N]``, ``a [N, C]`` float32
    (negative), ``d [C]``, ``h0 [R, N, C]`` float32, ``q_lens [R]``.
    Returns ``(m [R, Q, C]`` in ``x``'s dtype, the state after each
    row's last token ``[R, N, C]`` float32)``; slots at or past a row's
    length leave the state alone and their ``m`` is not meaningful.

    ``long_rows`` (static) promises that at most that many rows are
    longer than one token (a serving dispatch: a few prefill chunks
    beside a batch of decode rows). The first token of every row is then
    one step over all rows, and only the long rows, gathered, take the
    other ``Q - 1`` steps: every step reads and writes the states it
    advances, so this is ``R + (Q - 1) long_rows`` states moved and not
    ``Q R``."""
    with jax.named_scope(SCOPE):
        f32 = jnp.float32
        xf, dtf = x.astype(f32), dt.astype(f32)
        bf, cf = bmat.astype(f32), cmat.astype(f32)
        af, df = a.astype(f32), d.astype(f32)
        n = q_lens.astype(jnp.int32)

        def scan(h, n, first, xf, dtf, bf, cf):
            def tick(h, t):
                j, x_t, dt_t, b_t, c_t = t
                da = jnp.exp(dt_t[:, None, :] * af[None])
                new = da * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
                h = jnp.where((j < n)[:, None, None], new, h)
                y = jnp.sum(c_t[:, :, None] * h, axis=1) + df[None] * x_t
                return h, y

            per_tick = (first + jnp.arange(xf.shape[1], dtype=jnp.int32),) \
                + tuple(jnp.swapaxes(v, 0, 1) for v in (xf, dtf, bf, cf))
            h, ys = jax.lax.scan(tick, h, per_tick)
            return jnp.swapaxes(ys, 0, 1), h

        rows, q = x.shape[0], x.shape[1]
        if long_rows is None or q == 1 or long_rows >= rows:
            ys, h = scan(h0.astype(f32), n, 0, xf, dtf, bf, cf)
            return ys.astype(x.dtype), h
        # token 0 of every row, then tokens 1.. of the long rows alone
        y0, h = scan(h0.astype(f32), n, 0, *(v[:, :1]
                                             for v in (xf, dtf, bf, cf)))
        at = jnp.nonzero(n > 1, size=long_rows, fill_value=rows)[0]
        g = jnp.clip(at, 0, rows - 1)
        yl, hl = scan(h[g], jnp.where(at < rows, n[g], 0), 1,
                      *(v[g, 1:] for v in (xf, dtf, bf, cf)))
        h = h.at[at].set(hl, mode="drop")
        ys = jnp.concatenate(
            [y0, jnp.zeros((rows, q - 1, x.shape[2]), f32)], axis=1) \
            .at[at, 1:].set(yl, mode="drop")
        return ys.astype(x.dtype), h
