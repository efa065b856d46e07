"""A dispatch hands the step program ONE staged host buffer (ISSUE 31)
where it handed it one array a field. ``legacy_*`` below are the
engine's ``_sample_arrays``, ``_dispatch_rows``, ``_warm_mixed`` and
``_ensure_mixed_compiled`` as they stood before (commit 985d725), frozen
here word for word but for the three fields (``page_ids``, ``offs``,
``row_tok``) that PR 32 took away with the programs that read them: an
engine that runs them calls ``_mixed_forward`` with its 18 tensors, one
``jnp.asarray`` each. The packed engine must
leave the same tokens and the same pool bytes, bitwise, over runs that
mix chunked prefill with decode-only dispatches: float and int8 pages,
speculation, sampled rows, the latent/expert model."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor, no_grad
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.serving import LlamaServingEngine, Request
from paddle_tpu.jit import StaticFunction
from paddle_tpu.models import MlaMoeForCausalLM, tiny_mla_moe_config
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import metrics as _om
from paddle_tpu.observability.trace import span as _span


# ---------------------------------------------------------------------------
# the 18-argument dispatch, frozen
# ---------------------------------------------------------------------------
def legacy_sample_arrays(self, reqs, r_cap):
    b = self.sample_slots
    temps = np.zeros((r_cap,), np.float32)
    top_ps = np.ones((r_cap,), np.float32)
    top_ks = np.zeros((r_cap,), np.int32)
    seeds = np.zeros((r_cap,), np.int32)
    slot_ids = np.full((r_cap, b), -1, np.int32)
    slot_vals = np.zeros((r_cap, b), np.float32)
    cmodes = np.zeros((r_cap,), np.int32)
    if not self.sample_enabled:
        return (temps, top_ps, top_ks, seeds, slot_ids, slot_vals,
                cmodes)
    for i, r in enumerate(reqs):
        sp = r.sampling if r is not None else None
        if sp is None:
            continue
        temps[i] = sp.temperature
        top_ps[i] = sp.top_p
        top_ks[i] = sp.top_k
        seeds[i] = r._seed or 0
        bias = sp.logit_bias or {}
        allowed = None
        if sp.constraint is not None:
            try:
                allowed = sp.constraint(r.prompt_ids,
                                        tuple(r.output_ids))
            except Exception:
                self._m["constraint_errors"].inc()
                allowed = None
        if allowed is not None:
            ids = [int(tk) for tk in allowed]
            if not ids:
                # an empty allowed set has no valid continuation;
                # degrade to unconstrained rather than emit the
                # arbitrary all-masked argmax
                self._m["constraint_errors"].inc()
            elif len(ids) > b:
                self._m["constraint_truncated"].inc()
                ids = ids[:b]
            if ids:
                cmodes[i] = 1
                for j, tk in enumerate(ids):
                    slot_ids[i, j] = tk
                    slot_vals[i, j] = bias.get(tk, 0.0)
                continue
        if bias:
            for j, (tk, v) in enumerate(list(bias.items())[:b]):
                slot_ids[i, j] = int(tk)
                slot_vals[i, j] = v
    return temps, top_ps, top_ks, seeds, slot_ids, slot_vals, cmodes


def legacy_dispatch_rows(self, rows, cow):
    # speculative verify rows are multi-token decode rows: they
    # need the chunk-shaped program exactly like prefill chunks do
    needs_mixed = any(n > 1 or not is_dec
                      for _, _, _, n, _, is_dec in rows)
    if needs_mixed:
        t_cap, r_cap, qb = (self.chunk_budget, self.rows_cap,
                            self.chunk_block)
    else:
        t_cap, r_cap, qb = self.max_batch, self.max_batch, 1
    for old, new in cow:
        self._copy_page(old, new)
    key = ("mixed", t_cap)
    cold = key not in self._warmed_keys
    if cold and self._m["ttft"] is not _om.NULL:
        # compile this token shape OUTSIDE the TTFT window: a dummy
        # dispatch (all page writes land in the trash page, emitted
        # tokens discarded) triggers the one-time trace + compile,
        # and the affected clocks shift past it so TTFT keeps one
        # honest sample per request without the multi-second
        # compile skewing the histogram's +Inf bucket forever.
        # Under PADDLE_TPU_METRICS=0 this is skipped (zero-cost
        # mandate) and the cold dispatch just skips tpot.
        t_w = time.perf_counter()
        self._warm_mixed(t_cap)
        warm_dur = time.perf_counter() - t_w
        with self._lock:
            for r in {row[0] for row in rows}:
                if r._t_admit is not None:
                    r._t_admit += warm_dur
                if r._expires_at is not None:
                    # the deadline clock starts at admission;
                    # compile warmup is engine overhead, not
                    # request time
                    r._expires_at += warm_dur
        cold = False
    now = time.perf_counter()
    for r, _, _, _, _, is_dec in rows:
        if not is_dec and r._t_first_chunk is None:
            r._t_first_chunk = now
    # host-built metadata: reads of the allocator's tables are safe
    # here — cross-thread releases defer past the whole _entry
    tokens = np.zeros((1, t_cap), np.int64)
    pos = np.zeros((1, t_cap), np.int32)
    flat_idx = np.full((t_cap,), r_cap * qb - 1, np.int32)
    last_idx = np.zeros((r_cap,), np.int32)
    tables = np.full((r_cap, self.width), self.trash_page, np.int32)
    kv_lens = np.zeros((r_cap,), np.int32)
    q_starts = np.zeros((r_cap,), np.int32)
    q_lens = np.zeros((r_cap,), np.int32)
    # fused-write metadata: per row, the first position of its
    # sequence written by THIS dispatch, that position's packed
    # index, and the sequence's final kv_len (rows of one sequence
    # are consecutive, so one forward pass collects all three)
    w_starts = np.zeros((r_cap,), np.int32)
    w_flats = np.zeros((r_cap,), np.int32)
    w_ends = np.zeros((r_cap,), np.int32)
    seq_first: dict[int, tuple] = {}     # sid -> (w_start, w_flat)
    seq_last: dict[int, int] = {}        # sid -> w_end
    t = 0
    flat_start = []         # each row's first index in the T axis
    for i, (r, sid, start, n, toks, is_dec) in enumerate(rows):
        tb = self.alloc._tables[sid]
        tables[i, :len(tb)] = tb
        kv_lens[i] = start + n
        q_starts[i] = start
        q_lens[i] = n
        tokens[0, t:t + n] = toks
        pos[0, t:t + n] = start + np.arange(n)
        flat_idx[t:t + n] = i * qb + np.arange(n)
        flat_start.append(t)
        if sid not in seq_first:
            seq_first[sid] = (start, t)
        seq_last[sid] = start + n
        t += n
        last_idx[i] = t - 1
    for i, (r, sid, start, n, toks, is_dec) in enumerate(rows):
        w_starts[i], w_flats[i] = seq_first[sid]
        w_ends[i] = seq_last[sid]
    (temps, top_ps, top_ks, seeds, slot_ids, slot_vals,
     cmodes) = self._sample_arrays([row[0] for row in rows], r_cap)
    self._record_shape("mixed", t_cap)
    sf = self._ensure_mixed_compiled()
    self._arm_watchdog(cold)
    with self._lock:
        self._in_dispatch = True
    t0 = time.perf_counter()
    try:
        with no_grad(), _span("serving.mixed_step", rows=len(rows),
                              tokens=int(t), prefill=needs_mixed):
            nxt, new_k, new_v, new_ks, new_vs, stats = sf(
                Tensor(jnp.asarray(tokens)),
                Tensor(jnp.asarray(pos)),
                Tensor(jnp.asarray(flat_idx)),
                Tensor(jnp.asarray(last_idx)),
                Tensor(jnp.asarray(tables)),
                Tensor(jnp.asarray(kv_lens)),
                Tensor(jnp.asarray(q_starts)),
                Tensor(jnp.asarray(q_lens)),
                Tensor(jnp.asarray(w_starts)),
                Tensor(jnp.asarray(w_flats)),
                Tensor(jnp.asarray(w_ends)),
                Tensor(jnp.asarray(temps)),
                Tensor(jnp.asarray(top_ps)),
                Tensor(jnp.asarray(top_ks)),
                Tensor(jnp.asarray(seeds)),
                Tensor(jnp.asarray(slot_ids)),
                Tensor(jnp.asarray(slot_vals)),
                Tensor(jnp.asarray(cmodes)),
                self.k_pools, self.v_pools,
                self.k_scales, self.v_scales)
    finally:
        with self._lock:
            self._in_dispatch = False
        dur = time.perf_counter() - t0
        self._disarm_watchdog(dur, cold=cold)
        self._warmed_keys.add(key)
    self._note_mixed_bytes(t_cap)
    self._flush_deferred()
    self.k_pools, self.v_pools = list(new_k), list(new_v)
    if self.kv_quant:
        self.k_scales, self.v_scales = list(new_ks), list(new_vs)
    self._layer_stats = stats[0] if stats else None
    # the one edit: the caller now also asks for the bytes handed over,
    # (PR 33) for the rows that sample and (PR 36) for the rows on the
    # attention kernel's small tile
    return (nxt, flat_start, dur, cold, needs_mixed, t_cap, 0,
            int(np.count_nonzero(temps > 0)), None)


def legacy_warm_mixed(self, t_cap):
    t_cap = int(t_cap)
    if t_cap == self.chunk_budget:
        r_cap, qb = self.rows_cap, self.chunk_block
    elif t_cap == self.max_batch:
        r_cap, qb = self.max_batch, 1
    else:
        return False
    sf = self._ensure_mixed_compiled()
    samp = self._sample_arrays([], r_cap)
    with no_grad():
        _, wk, wv, wks, wvs, _ = sf(
            Tensor(jnp.asarray(np.zeros((1, t_cap), np.int64))),
            Tensor(jnp.asarray(np.zeros((1, t_cap), np.int32))),
            Tensor(jnp.asarray(np.zeros((t_cap,), np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            Tensor(jnp.asarray(np.full((r_cap, self.width),
                                       self.trash_page, np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            Tensor(jnp.asarray(np.zeros((r_cap,), np.int32))),
            *[Tensor(jnp.asarray(a)) for a in samp],
            self.k_pools, self.v_pools,
            self.k_scales, self.v_scales)
    self.k_pools, self.v_pools = list(wk), list(wv)
    if self.kv_quant:
        self.k_scales, self.v_scales = list(wks), list(wvs)
    self._warmed_keys.add(("mixed", t_cap))
    self._warm_dispatches += 1
    self._record_shape("mixed", t_cap)
    self._note_mixed_bytes(t_cap)
    return True


def legacy_ensure_mixed_compiled(self):
    if self._mixed_static is None:
        self._mixed_static = StaticFunction(
            self._mixed_forward, state=[self.model], warmup="once",
            donate=False, donate_inputs=True,
            name="serving.mixed_step")
        self._mixed_static._warmed_any = True
    return self._mixed_static


LEGACY = {"_sample_arrays": legacy_sample_arrays,
          "_dispatch_rows": legacy_dispatch_rows,
          "_warm_mixed": legacy_warm_mixed,
          "_ensure_mixed_compiled": legacy_ensure_mixed_compiled}


# ---------------------------------------------------------------------------
# the same traffic through both
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256))
    m.eval()
    return m


@pytest.fixture(scope="module")
def latent():
    paddle.seed(0)
    m = MlaMoeForCausalLM(tiny_mla_moe_config())
    m.eval()
    return m


def _only_evens(prompt_ids, output_ids):
    return [2, 4, 6, 8] if len(output_ids) % 2 else None


def _sampled():
    """One request of each sampler feature beside two greedy ones."""
    return [SamplingParams(temperature=0.8, top_p=0.9, seed=11),
            None,
            SamplingParams(temperature=1.3, top_k=5, seed=12,
                           logit_bias={3: 2.5, 200: -4.0}),
            SamplingParams(temperature=0.7, seed=13,
                           constraint=_only_evens),
            SamplingParams(logit_bias={7: 50.0}),
            None]


def _run(model, params=None, new=7, **kw):
    """Requests that arrive while others decode: the run's dispatches are
    chunked prefill alone, prefill beside decode rows, and decode-only
    steps. Returns (outputs, pool bytes, dispatch kinds, buffers seen)."""
    kw = dict(dict(max_batch=4, page_size=8, num_pages=65,
                   max_pages_per_seq=16, chunk_budget=16, chunk_block=8),
              **kw)
    e = LlamaServingEngine(model, **kw)
    kinds, bufs = [], []
    rows_of = e._dispatch_rows

    def spy(rows, cow):
        kinds.append("mixed" if any(n > 1 or not dec for *_, n, _, dec
                                    in rows) else "decode")
        return rows_of(rows, cow)

    e._dispatch_rows = spy
    if hasattr(e, "_run_mixed"):
        run_of = e._run_mixed

        def spy_run(buf):
            bufs.append((buf.copy(), len(kinds)))
            return run_of(buf)

        e._run_mixed = spy_run
    rng = np.random.RandomState(31)
    vocab = model.config.vocab_size
    # repeated patterns give the n-gram drafter something to accept
    prompts = [rng.randint(0, vocab, (n,)).tolist() for n in (30, 5, 19)] \
        + [[5, 6, 7, 8] * 5, rng.randint(0, vocab, (12,)).tolist(),
           [9, 10] * 4]
    params = params or [None] * len(prompts)
    reqs = [Request(p, max_new_tokens=new, sampling=sp)
            for p, sp in zip(prompts, params)]
    pending = list(reqs)
    for _ in range(2):
        e.add_request(pending.pop(0))
    steps = 0
    while pending or any(not r.done for r in reqs):
        e.step()
        steps += 1
        assert steps < 400
        # the next one arrives when a row is free and the others are
        # well into their decode
        if pending and steps % 5 == 0 \
                and sum(not r.done for r in reqs if r.seq_id is not None) \
                < kw["max_batch"]:
            e.add_request(pending.pop(0))
    assert all(r.status == "completed" for r in reqs)
    outs = [list(r.output_ids) for r in reqs]
    state = [np.asarray(p._data) for pools in (
        e.k_pools, e.v_pools, e.k_scales, e.v_scales) for p in pools]
    layouts = dict(e._layouts)
    e.close()
    return outs, state, kinds, bufs, layouts


def _both(model, monkeypatch, **kw):
    new = _run(model, **kw)
    with monkeypatch.context() as mp:
        for name, fn in LEGACY.items():
            mp.setattr(LlamaServingEngine, name, fn)
        mp.delattr(LlamaServingEngine, "_run_mixed")
        old = _run(model, **kw)
    return new, old


def _same(new, old, pools):
    assert new[0] == old[0]                             # tokens
    assert new[2] == old[2]                             # dispatch by dispatch
    assert {"mixed", "decode"} <= set(new[2])
    assert len(new[1]) == len(old[1]) == pools
    for a, b in zip(new[1], old[1]):
        # the trash page (the last) collects the padding's writes too
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert not old[3] and len(new[3]) >= len(new[2])


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("program", [dict()], ids=["rope_fused"])
def test_packed_dispatch_is_the_18_argument_dispatch(llama, monkeypatch,
                                                     program, kv_dtype):
    new, old = _both(llama, monkeypatch, kv_dtype=kv_dtype, **program)
    _same(new, old, 8 if kv_dtype else 4)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_packed_dispatch_with_speculation(llama, monkeypatch, kv_dtype):
    new, old = _both(llama, monkeypatch, kv_dtype=kv_dtype, spec_k=3,
                     new=12)
    _same(new, old, 8 if kv_dtype else 4)


@pytest.mark.parametrize("spec_k", [0, 3])
def test_packed_dispatch_with_sampled_rows(llama, monkeypatch, spec_k):
    """Temperature, top-p, top-k, a logit bias and a constraint ride as
    bit patterns in the same buffer as the greedy rows' zeros."""
    new, old = _both(llama, monkeypatch, params=_sampled(),
                     spec_k=spec_k, new=9)
    _same(new, old, 4)
    outs = new[0]
    assert all(t in (2, 4, 6, 8) for t in outs[3][1::2])  # the constraint
    assert outs[4] == [7] * 9                           # the bias, greedy


def test_packed_dispatch_of_the_latent_expert_model(latent, monkeypatch):
    new, old = _both(latent, monkeypatch)
    _same(new, old, len(latent.model.layers))   # one latent pool a layer


def test_packed_dispatch_of_an_engine_without_the_sampler(
        llama, monkeypatch):
    """``sampling=False`` compiles the argmax-only program: the seven
    sampler fields still ride, at their fill values, and are not read."""
    new, old = _both(llama, monkeypatch, sampling=False)
    _same(new, old, 4)


def test_unused_slots_of_every_dispatch_read_their_fill(llama):
    """Each buffer the program was handed: rows and tokens past the
    dispatch's own are at their fill values, whatever the dispatch
    before it wrote (a many-row mixed dispatch comes before decode-only
    ones of fewer rows, and the other way round)."""
    outs, _, kinds, bufs, layouts = _run(llama, params=_sampled(), new=9)
    by_size = {lay.size: lay for lay in layouts.values()}
    assert len(by_size) == 2
    seen = set()
    for buf, _ in bufs:
        lay = by_size[buf.size]
        t_cap, r_cap, qb, width, b = lay.shape
        f = lay.views(buf)
        rows = int((f["q_lens"] > 0).sum())
        toks = int(f["q_lens"].sum())
        seen.add((lay.shape[0], rows))
        assert (f["q_lens"][:rows] > 0).all()           # rows lie first
        assert (f["tokens"][0, toks:] == 0).all()
        assert (f["pos"][0, toks:] == 0).all()
        assert (f["flat_idx"][toks:] == r_cap * qb - 1).all()
        assert (f["tables"][rows:] == 64).all()          # the trash page
        for i in range(rows):
            # a sequence's pages first (at least its context's), then
            # the trash page to the table's end
            held = int((f["tables"][i] != 64).sum())
            assert held >= -(-int(f["kv_lens"][i]) // 8)
            assert (f["tables"][i, held:] == 64).all()
        for name in ("last_idx", "kv_lens", "q_starts", "w_starts",
                     "w_flats", "w_ends", "temps", "top_ks", "seeds",
                     "cmodes", "slot_vals"):
            assert (f[name][rows:] == 0).all(), name
        assert (f["top_ps"][rows:] == 1.0).all()
        assert (f["slot_ids"][rows:] == -1).all()
        # a loop of step() writes every token on the host
        assert (f["prev_idx"] == -1).all()
    # both program shapes, and dispatches of few rows after many
    assert {t for t, _ in seen} == {16, 4}
    assert len({r for _, r in seen}) >= 3


def test_warm_dispatch_goes_through_the_layout(llama):
    """``_warm_mixed`` hands the program a blank buffer of the same
    layout (one compiled program a shape, no third copy of the argument
    list) and refuses a token count that is neither shape."""
    e = LlamaServingEngine(llama, max_batch=4, page_size=8, num_pages=65,
                           max_pages_per_seq=16, chunk_budget=16,
                           chunk_block=8)
    seen = []
    run_of = e._run_mixed
    e._run_mixed = lambda buf: (seen.append(buf.copy()), run_of(buf))[1]
    warmed = e.prewarm(mixed=[16, 4, 7])["mixed"]
    assert sorted(warmed) == [4, 16] and len(seen) == 2
    for b, t in zip(seen, warmed):
        assert np.array_equal(b, e._dispatch_layout(t).new())
    assert e._dispatch_layout(7) is None
    r = Request([1, 2, 3, 4, 5], max_new_tokens=3)
    e.add_request(r)
    while not r.done:
        e.step()
    # the prewarmed programs are the ones traffic runs: no third compile
    assert len(e._mixed_static._cache) == 2 and len(seen) == 2 + 3
    e.close()


def test_a_token_the_device_holds_rides_as_its_index(llama):
    """ISSUE 38's field, ``prev_idx``: the same requests through a loop
    one dispatch ahead and through a loop of ``step()`` plan the same
    rows dispatch by dispatch, and their buffers are equal bitwise field
    by field, but for a decode row's input token: the ahead loop built
    the buffer before that token reached the host, so ``tokens`` holds 0
    there and ``prev_idx`` the row of the dispatch before that computes
    it (the same sequence's row: same pages), where the step loop wrote
    the token itself and -1. The tokens served are the same."""
    kw = dict(max_batch=4, page_size=8, num_pages=65, max_pages_per_seq=16,
              chunk_budget=16, chunk_block=8, prefix_cache=False)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, (n,)).tolist() for n in (30, 5, 19, 12)]

    def run(ahead):
        e = LlamaServingEngine(llama, **kw)
        e.prewarm(mixed=[16, 4])
        bufs, run_of = [], e._run_mixed
        e._run_mixed = lambda buf: (bufs.append(buf.copy()),
                                    run_of(buf))[1]
        reqs = [Request(p, max_new_tokens=3 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            e._admit(r)
        while any(not r.done for r in reqs):
            (e.step_ahead if ahead else e.step)()
        layouts = {lay.size: lay for lay in e._layouts.values()}
        assert e._inflight is None
        e.close()
        return [layouts[b.size].views(b) for b in bufs], \
            [list(r.output_ids) for r in reqs], layouts

    got, outs, layouts = run(True)
    want, outs_step, _ = run(False)
    assert outs == outs_step and len(got) == len(want) > 6
    fed_total = 0
    for k, (a, b) in enumerate(zip(got, want)):
        assert a.keys() == b.keys()
        for name in a:
            if name not in ("tokens", "prev_idx"):
                assert np.array_equal(a[name], b[name]), (k, name)
        assert (b["prev_idx"] == -1).all()
        fed = a["prev_idx"] >= 0
        assert np.array_equal(a["tokens"][~fed], b["tokens"][~fed])
        assert (a["tokens"][fed] == 0).all()
        # exactly the decode rows (a row of one token past its prompt's
        # end... every one-token row that writes past position 0 here)
        qb = next(lay for lay in layouts.values()
                  if lay.shape[0] == a["tokens"].shape[1]).shape[2]
        for t in np.flatnonzero(fed[0]):
            row, src = a["flat_idx"][t] // qb, a["prev_idx"][0, t]
            assert a["q_lens"][row] == 1 and k > 0
            before = got[k - 1]
            assert before["q_lens"][src] > 0
            # the same sequence: the producing row holds the same pages
            held = -(-int(before["kv_lens"][src]) // 8)
            assert np.array_equal(before["tables"][src, :held],
                                  a["tables"][row, :held])
            assert before["kv_lens"][src] == a["q_starts"][row]
        fed_total += int(fed.sum())
    # every decode row but none: each request's decode rows all follow
    # the dispatch that computed their token
    assert fed_total == sum(len(o) - 1 for o in outs)
