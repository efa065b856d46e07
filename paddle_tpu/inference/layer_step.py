"""What one packed serving step gives every decoder layer.

`LlamaServingEngine`'s two step programs (the mixed step and the decode
scan, which scans the mixed step) own the batching: which tokens are
packed, which pages they write, the block tables. A layer owns its
mathematics: it states what it keeps per token (``serving_cache()``: a
list of ``(heads, width)``, one entry a pool; ``heads`` None for a pool
with no head axis) and runs its own step over its pages
(``serving_step(x, step, pages) -> (x, pages, stats)``) from the
`ServingStep` the engine hands it. ``pages`` are the layer's pools in
the order it stated them (then their int8 scale sidecars where the
engine quantizes pages); ``stats`` is None or a small int32 array the
host reads with the tokens.

Layers of one model may keep different things. Beside the list above
(every layer alike: the Llama and the latent layers' statement), a layer
may state one of:

- `PagedKV` ``(heads, width, window=None)``: a K and a V pool ``[P,
  heads, page, width]``. Without a window they hold the whole context
  behind the engine's block tables; with one, a RING of pages a sequence
  slot (``ServingStep.ring_tables``) that holds the window, one
  dispatch's chunk and a page, whatever the context;
- `PagedLatent` ``(width)``: ONE pool ``[P, page, width]`` with no head
  axis (a latent row all heads share), the whole context behind the
  engine's block tables: what a model whose layers are all latent states
  as ``[(None, width)]``, for a layer beside layers of other kinds;
- `SlotState` ``(shapes)``: arrays of fixed shape a sequence slot
  (``[slots + 1, *shape]``, the last slot for rows that are none), which
  live and die with the sequence (``ServingStep.slots``);
- `SharedPages` ``(layer)``: it keeps nothing and reads (never writes)
  the pools of layer ``layer``, as that layer's step left them;
- ``None``: it keeps nothing.

A layer may also hand something on to later layers of the same step:
``serving_step`` then returns a fourth value, a dict, which the engine
merges into ``ServingStep.carry``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import run_op
from ..ops.ragged_paged_attention import rope_tables

__all__ = ["DispatchLayout", "ServingStep", "PagedKV", "PagedLatent",
           "SlotState", "SharedPages"]


class PagedKV:
    """A K and a V pool of ``heads`` heads ``width`` wide; with
    ``window`` only the last ``window`` tokens are ever read."""

    def __init__(self, heads, width, window=None):
        self.heads, self.width, self.window = int(heads), int(width), window


class PagedLatent:
    """One pool of rows ``width`` wide that all heads share, the whole
    context."""

    def __init__(self, width):
        self.width = int(width)


class SlotState:
    """Arrays a sequence keeps whatever its length: ``shapes`` is a list
    of ``(shape, dtype)``, one pool each."""

    def __init__(self, shapes):
        self.shapes = [(tuple(s), jnp.dtype(d)) for s, d in shapes]


class SharedPages:
    """This layer reads the pools that layer ``layer`` keeps."""

    def __init__(self, layer):
        self.layer = int(layer)


def _token_gather(x, idx):
    """Gather rows of ``x`` by an integer index array: the mixed
    program's unpack from the ragged kernel's row-blocked layout
    [R, QB, ...] to the flat token axis [T, ...]."""
    def fn(x, idx):
        return x[idx.astype(jnp.int32)]

    return run_op("serving_token_gather", fn, (x, idx),
                  differentiable=False)


class DispatchLayout:
    """Where each host-built field of one dispatch lies in the ONE flat
    int32 buffer the host hands the step program: the 18 arrays
    :meth:`LlamaServingEngine._mixed_forward` takes before its pools,
    in its argument order, then ``prev_idx`` (and, of a model that keeps
    a state or a ring a sequence slot, ``slots``), back to back with no
    padding. ``prev_idx [1, T]`` says where a packed token comes from:
    -1, the host wrote it into ``tokens``; ``i >= 0``, it is entry ``i``
    of the tokens the dispatch before this one returned, which never
    left the device (a decode row launched before the host had read
    them). The host fills
    :meth:`views` of a buffer from :meth:`new` and transfers it once;
    the program takes it apart again with :meth:`unpack` at static
    offsets. The float32 fields ride as their bit patterns (a numpy
    view on the host, a bitcast on the device: exact).

    ``t_cap`` packed tokens, ``r_cap`` rows of at most ``qb`` query
    tokens, tables ``width`` wide, ``sample_slots`` bias slots a row;
    ``trash_page`` fills what no row claims."""

    def __init__(self, t_cap, r_cap, qb, width, sample_slots, trash_page,
                 trash_slot=None):
        t, r, b = int(t_cap), int(r_cap), int(sample_slots)
        i32, f32 = np.int32, np.float32
        # (name, shape, dtype, what an unused slot reads)
        spec = (
            ("tokens", (1, t), i32, 0),
            ("pos", (1, t), i32, 0),
            ("flat_idx", (t,), i32, r * int(qb) - 1),
            ("last_idx", (r,), i32, 0),
            ("tables", (r, int(width)), i32, trash_page),
            ("kv_lens", (r,), i32, 0),
            ("q_starts", (r,), i32, 0),
            ("q_lens", (r,), i32, 0),
            ("w_starts", (r,), i32, 0),
            ("w_flats", (r,), i32, 0),
            ("w_ends", (r,), i32, 0),
            ("temps", (r,), f32, 0.0),
            ("top_ps", (r,), f32, 1.0),
            ("top_ks", (r,), i32, 0),
            ("seeds", (r,), i32, 0),
            ("slot_ids", (r, b), i32, -1),
            ("slot_vals", (r, b), f32, 0.0),
            ("cmodes", (r,), i32, 0),
            ("prev_idx", (1, t), i32, -1),
        )
        if trash_slot is not None:
            # a model whose layers keep a state or a ring a sequence
            # slot: each row's slot, a 20th field
            spec += (("slots", (r,), i32, int(trash_slot)),)
        self.shape = (t, r, int(qb), int(width), b)
        fields, at = [], 0
        for name, shape, dtype, _ in spec:
            end = at + int(np.prod(shape))
            fields.append((name, at, end, shape, np.dtype(dtype)))
            at = end
        #: ``(name, start, stop, shape, dtype)`` of every field, in words
        self.fields = tuple(fields)
        self.size = at
        self.nbytes = 4 * at
        blank = np.empty((at,), np.int32)
        views = self.views(blank)
        for name, _, _, fill in spec:
            views[name][...] = fill
        blank.setflags(write=False)
        self._blank = blank

    def new(self):
        """A fresh host buffer with every field at its fill value. One
        a dispatch: the transfer may read the host memory after
        ``device_put`` returns, and the CPU backend may alias it."""
        return self._blank.copy()

    def views(self, buf):
        """``{name: array}``: each field as a writable view of ``buf``
        in its own shape and dtype."""
        return {name: buf[at:end].view(dtype).reshape(shape)
                for name, at, end, shape, dtype in self.fields}

    def unpack(self, packed):
        """The fields of a device buffer ``[size]`` int32, as a tuple in
        order (traceable: static slices, reshapes and bitcasts)."""
        out = []
        for _, at, end, shape, dtype in self.fields:
            a = packed[at:end].reshape(shape)
            if dtype != np.int32:
                a = jax.lax.bitcast_convert_type(a, dtype)
            out.append(a)
        return tuple(out)


class ServingStep:
    """The metadata of one packed step (see `LlamaServingEngine.
    _mixed_forward` for the shapes): ``tokens`` packed tokens in
    ``rows`` rows of at most ``qblock`` query tokens, and what the
    engine decided of its pools (``kv_quant``, ``trash_page``). Tables
    a layer kind needs (rotary sin/cos, a window's ring tables) are made
    once a step and shared by its layers. ``slots [R]`` (None for a
    model that keeps no state or ring) is each row's sequence slot;
    ``carry`` holds what earlier layers of this step handed on."""

    def __init__(self, engine, qblock, pos, flat_idx, tables, kv_lens,
                 q_starts, q_lens, w_starts, w_flats, w_ends, slots=None):
        self.slots, self.carry = slots, {}
        self._ring_pages = engine.ring_pages
        #: of an engine that prefills one chunk a sequence a dispatch:
        #: the most rows of a dispatch that are longer than one token
        self.chunk_rows = engine.chunk_rows
        self.pos, self.flat_idx, self.tables = pos, flat_idx, tables
        self.kv_lens, self.q_starts, self.q_lens = kv_lens, q_starts, q_lens
        self.w_starts, self.w_flats, self.w_ends = w_starts, w_flats, w_ends
        self.tokens = pos.shape[1]
        self.rows, self.qblock = tables.shape[0], int(qblock)
        self.kv_quant, self.trash_page = engine.kv_quant, engine.trash_page
        self._tables = {}

    def _shared(self, key, name, fn, *args):
        if key not in self._tables:
            self._tables[key] = run_op(name, fn, args, differentiable=False)
        return self._tables[key]

    def rope(self, head_dim, base):
        """Rotary sin/cos ``[T, D]`` f32, one row a packed token, in the
        duplicated-half layout: bitwise the values
        `fused_rotary_position_embedding` derives from ``position_ids``."""
        return self._shared(("neox", head_dim, base), "serving_rope_tables",
                            lambda p: rope_tables(p, head_dim, base),
                            self.pos)

    def rope_interleaved(self, dim, base):
        """Rotary sin/cos ``[T, dim/2]`` f32 for pairs ``(2i, 2i+1)``."""
        from ..models.mla_moe import rope_tables_interleaved
        return self._shared(
            ("pairs", dim, base), "serving_rope_tables_interleaved",
            lambda p: rope_tables_interleaved(p, dim, base), self.pos)

    def no_rope(self, head_dim):
        """sin 0 / cos 1 ``[T, D]`` f32: the rotation that leaves every
        value as it is (``x * 1 + rot(x) * 0``, exact), for layers
        without positional encoding."""
        t = self.tokens
        return self._shared(
            ("norope", head_dim), "serving_no_rope",
            lambda p: (jnp.zeros((t, head_dim), jnp.float32),
                       jnp.ones((t, head_dim), jnp.float32)), self.pos)

    def ring_tables(self, window):
        """``[R, W]`` tables of the layers that keep ``window`` keys:
        logical page ``p`` of the sequence in slot ``s`` lies in page
        ``s * ring + p % ring`` of their pools, so a page behind the
        window is the page a later token overwrites. No allocator and
        nothing from the host but the slots."""
        ring, width = self._ring_pages(window), self.tables.shape[1]
        return self._shared(
            ("ring", window), "serving_ring_tables",
            lambda s: s.astype(jnp.int32)[:, None] * ring
            + (jnp.arange(width, dtype=jnp.int32) % ring)[None, :],
            self.slots)

    def row_index(self):
        """``[R, QB]`` packed index of every row's slots (row ``r``'s
        tokens sit back to back from ``w_flats + q_starts - w_starts``)."""
        from ..ops.selective_scan import row_index
        t, qb = self.tokens, self.qblock
        return self._shared(
            "rows", "serving_row_index",
            lambda wf, qs, ws: row_index(wf + qs - ws, qb, t),
            self.w_flats, self.q_starts, self.w_starts)

    def token_valid(self):
        """``[T]`` bool: the packed tokens that belong to a row (they
        sit back to back from index 0; the rest is padding)."""
        t = self.tokens
        return self._shared(
            "valid", "serving_token_valid",
            lambda ql: jnp.arange(t, dtype=jnp.int32)
            < jnp.sum(ql.astype(jnp.int32)), self.q_lens)

    def unpack(self, x):
        """Flattened row blocks ``[R * QB, ...]`` -> ``[T, ...]``."""
        return _token_gather(x, self.flat_idx)
