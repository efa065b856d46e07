"""The continuous loops run ONE DISPATCH AHEAD (ISSUE 38):
``engine.step_ahead()`` plans, builds and enqueues dispatch n+1 and only
then waits for dispatch n's tokens, a decode row of n+1 taking its input
token from n's output on the device. Held here, on the CPU at tiny
sizes: the ahead loop emits, request by request, the tokens a loop of
``engine.step()`` emits (a Llama engine, the hybrid family's slotted
engine, a latent expert engine; budgets of 1 and 2 tokens among them);
a sequence that ends while its next row is in flight (EOS, a stop token,
a cancel, a deadline, an eviction) leaves one STALE row, counted and
dropped, its output where the synchronous engine's ends and its pages
and slot back with the allocator; an engine or a request whose next plan
reads the host's view of the last token (speculation, a constraint
hook, the host tier) never runs ahead; and ``step``, ``decode_many``,
``drain``, ``close`` and a raised ``serve.decode`` fault leave nothing
in flight."""

import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.serving import LlamaServingEngine, Request
from paddle_tpu.models import (MlaMoeForCausalLM, SambaYForCausalLM,
                               tiny_mla_moe_config, tiny_sambay_config)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import trace as otrace
from paddle_tpu.testing import faults

GEOMETRY = dict(max_batch=4, page_size=8, num_pages=65,
                max_pages_per_seq=16, chunk_block=8)
BUDGET = {"llama": 16, "hybrid": 32, "latent": 16}


@pytest.fixture(scope="module")
def models():
    paddle.seed(0)
    made = {"llama": LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256)),
        "hybrid": SambaYForCausalLM(tiny_sambay_config()),
        "latent": MlaMoeForCausalLM(tiny_mla_moe_config())}
    for m in made.values():
        m.eval()
    return made


def _engine(models, kind, warm=True, **kw):
    e = LlamaServingEngine(models[kind], chunk_budget=BUDGET[kind],
                           **dict(GEOMETRY, **kw))
    if warm:
        # both program shapes compiled: the loop may run ahead from its
        # first dispatch (a shape's first dispatch is synchronous)
        e.prewarm(mixed=[e.chunk_budget, e.max_batch])
    return e


def _prompts(models, kind, lengths, seed=7):
    rng = np.random.RandomState(seed)
    vocab = models[kind].config.vocab_size
    return [rng.randint(1, vocab, (n,)).tolist() for n in lengths]


def _dispatches():
    return [e["args"] for e in otrace.get_events()
            if e["name"] == "serving.dispatch"]


def _drive(e, reqs, turn, between=None):
    """Admit ``reqs`` as rows free up and call ``turn`` until all are
    done; ``between(turns)`` runs after every turn. Returns the
    `serving.dispatch` spans' args."""
    otrace.clear()
    pending, turns = list(reqs), 0
    while pending or any(not r.done for r in reqs) or e._requeue:
        while pending and len(e._live) < e.max_batch:
            e._admit(pending.pop(0))
        turn()
        turns += 1
        assert turns < 600
        if between is not None:
            between(turns)
    return _dispatches()


def _idle(e):
    """Nothing in flight, and everything back with the allocator."""
    assert e._inflight is None and not e._live
    if e.prefix is not None:
        e.prefix.clear()
    assert e.alloc.free_pages == e.alloc.num_pages
    if e._slotted:
        assert e.alloc.slots_held == 0


# ---------------------------------------------------------------------------
# the same tokens, request by request
# ---------------------------------------------------------------------------
LENGTHS = (30, 5, 19, 12, 40, 9)        # 30, 19, 40: several chunks


@pytest.mark.parametrize("new", [1, 2, 7])
@pytest.mark.parametrize("kind", ["llama", "hybrid", "latent"])
def test_the_ahead_loop_emits_what_the_step_loop_emits(models, kind, new):
    """Six requests through four rows: chunked prefill beside decode
    rows, final chunks that turn into decode rows one dispatch on,
    decode-only dispatches, requests that end after 1 and 2 tokens (no
    row is planned for a request whose last token is in flight)."""
    outs, disp = {}, {}
    for mode in ("ahead", "step"):
        e = _engine(models, kind)
        reqs = [Request(p, max_new_tokens=new + (i % 2) * (new > 2))
                for i, p in enumerate(_prompts(models, kind, LENGTHS))]
        disp[mode] = _drive(e, reqs, e.step_ahead if mode == "ahead"
                            else e.step)
        assert all(r.status == "completed" for r in reqs)
        outs[mode] = [list(r.output_ids) for r in reqs]
        assert [len(o) for o in outs[mode]] \
            == [r.max_new_tokens for r in reqs]
        _idle(e)
        # two programs, whatever the order of shapes
        assert len(e._mixed_static._cache) == 2
        e.close()
    assert outs["ahead"] == outs["step"]
    for d in disp["step"]:
        assert d["ahead"] == d["dev_tokens"] == d["stale_rows"] == 0
    ahead = disp["ahead"]
    # the first dispatch of a run has no predecessor in flight; the run
    # breaks only where every live request's last token is in flight
    assert ahead[0]["ahead"] == 0
    assert sum(d["ahead"] for d in ahead) >= len(ahead) // 2
    assert all(d["stale_rows"] == 0 for d in ahead)
    # behind a dispatch in flight every decode row's input token is the
    # one that dispatch computes: it is taken on the device
    assert all(d["dev_tokens"] == d["ahead"] * d["decode_rows"]
               for d in ahead)
    assert sum(d["tokens"] for d in ahead) \
        == sum(d["tokens"] for d in disp["step"])
    if new > 1:
        assert sum(d["dev_tokens"] for d in ahead) > 0


def test_arrivals_between_turns_join_the_next_plan(models):
    """A request admitted while a dispatch is in flight gets its first
    chunk in the dispatch planned next, behind the one in flight; its
    tokens are what ``model.generate`` gives, whatever the loop."""
    e = _engine(models, "llama")
    prompts = _prompts(models, "llama", (21, 6, 33))
    reqs = [Request(p, max_new_tokens=6) for p in prompts]
    e._admit(reqs[0])
    otrace.clear()
    for i in range(200):
        if i in (2, 5):
            assert e._inflight is not None
            e._admit(reqs[1 if i == 2 else 2])
        if e.step_ahead() == 0:
            break
    assert all(r.status == "completed" for r in reqs)
    want = [models["llama"].generate(
        paddle.to_tensor(np.asarray([p], np.int64)), max_new_tokens=6)
            for p in prompts]
    for r, w, p in zip(reqs, want, prompts):
        assert list(r.output_ids) == np.asarray(w._data)[0, len(p):].tolist()
    _idle(e)
    e.close()


# ---------------------------------------------------------------------------
# a sequence ends while its next row is in flight
# ---------------------------------------------------------------------------
def _reference(models, kind, prompts, new):
    e = _engine(models, kind, warm=False)
    reqs = [Request(p, max_new_tokens=new) for p in prompts]
    _drive(e, reqs, e.step)
    e.close()
    return [list(r.output_ids) for r in reqs]


@pytest.mark.parametrize("ending", ["eos", "stop", "cancel", "deadline",
                                    "evict"])
@pytest.mark.parametrize("kind", ["llama", "hybrid"])
def test_an_ending_in_flight_leaves_one_stale_row(models, kind, ending):
    """Request A ends after its ``cut``-th token while the dispatch
    with its next decode row is already enqueued: that row is stale. It
    is counted (the span's ``stale_rows``, the
    ``serving_dispatch_stale_rows_total`` counter), nothing of it is
    applied (A's output ends where the synchronous engine's does; B,
    decoding beside it, is untouched), and A's pages and slot go back
    with its release."""
    om.default_registry().clear()
    new = 9
    # (seeds whose greedy continuation of A brings a new token then:
    # the tiny hybrid model likes to repeat itself)
    prompts = _prompts(models, kind, (13, 22),
                       seed={"llama": 13, "hybrid": 14}[kind])
    ref = _reference(models, kind, prompts, new)
    # the first token of A's that it has not emitted before (an EOS
    # ends the request at its FIRST occurrence)
    cut = next(i for i in range(2, new - 2) if ref[0][i] not in ref[0][:i])
    want = {"eos": ref[0][:cut + 1], "stop": ref[0][:cut],
            "cancel": ref[0][:cut], "deadline": ref[0][:cut],
            "evict": ref[0]}[ending]
    status = {"cancel": "cancelled", "deadline": "deadline_exceeded"}
    outs, disp, stale = {}, {}, {}
    for mode in ("ahead", "step"):
        e = _engine(models, kind)
        a = Request(prompts[0], max_new_tokens=new,
                    eos_token_id=ref[0][cut] if ending == "eos" else None,
                    stop=[ref[0][cut]] if ending == "stop" else ())
        b = Request(prompts[1], max_new_tokens=new)
        fired = []

        def between(turns, e=e, a=a):
            # the host has seen ``cut`` of A's tokens; in the ahead loop
            # the dispatch that computes the next one is in flight
            if fired or len(a.output_ids) != cut:
                return
            fired.append(turns)
            if mode == "ahead":
                assert e._inflight is not None and any(
                    row[0] is a for row in e._inflight.rows)
            if ending == "cancel":
                assert e.cancel(a)
            elif ending == "deadline":
                a._expires_at = time.perf_counter() - 1.0
            elif ending == "evict":
                e._evict(a)

        before = e._m["stale_rows"].value
        disp[mode] = _drive(e, [a, b], e.step_ahead if mode == "ahead"
                            else e.step, between)
        stale[mode] = e._m["stale_rows"].value - before
        assert a.status == status.get(ending, "completed")
        assert b.status == "completed"
        outs[mode] = [list(a.output_ids), list(b.output_ids)]
        _idle(e)
        e.close()
    assert outs["ahead"] == outs["step"] == [want, ref[1]]
    assert sum(d["stale_rows"] for d in disp["step"]) == stale["step"] == 0
    assert sum(d["stale_rows"] for d in disp["ahead"]) \
        == stale["ahead"] == 1
    # the stale row took its token on the device like any other
    d = next(d for d in disp["ahead"] if d["stale_rows"])
    assert d["ahead"] == 1 and d["dev_tokens"] == d["decode_rows"] >= 1


# ---------------------------------------------------------------------------
# where the plan needs the host's view of the last token
# ---------------------------------------------------------------------------
def _only_small(prompt_ids, output_ids):
    return [2, 4, 6, 8] if len(output_ids) % 2 else None


@pytest.mark.parametrize("case", ["spec_k", "constraint", "kv_tier"])
def test_a_plan_that_reads_the_last_token_is_synchronous(models, case):
    kw = {"spec_k": dict(spec_k=3), "kv_tier": dict(kv_tier=True)}
    sp = SamplingParams(temperature=0.7, seed=13, constraint=_only_small)
    prompts = [[5, 6, 7, 8] * 5] + _prompts(models, "llama", (17,))
    outs = {}
    for mode in ("ahead", "step"):
        e = _engine(models, "llama", **kw.get(case, {}))
        reqs = [Request(p, max_new_tokens=10,
                        sampling=sp if case == "constraint" and i == 0
                        else None) for i, p in enumerate(prompts)]
        disp = _drive(e, reqs, e.step_ahead if mode == "ahead" else e.step)
        assert disp and all(d["ahead"] == d["dev_tokens"] == 0
                            for d in disp)
        outs[mode] = [list(r.output_ids) for r in reqs]
        _idle(e)
        e.close()
    assert outs["ahead"] == outs["step"]
    if case == "constraint":
        assert all(t in (2, 4, 6, 8) for t in outs["ahead"][0][1::2])


def test_a_constraint_hook_holds_the_loop_only_while_it_lives(models):
    """The loop runs ahead again once the request with the hook is
    done: what is observed is the live set, not an option."""
    sp = SamplingParams(temperature=0.7, seed=13, constraint=_only_small)
    e = _engine(models, "llama")
    hooked = Request([3, 1, 4, 1, 5], max_new_tokens=3, sampling=sp)
    plain = Request(_prompts(models, "llama", (11,))[0], max_new_tokens=12)
    seen = []
    disp = _drive(e, [hooked, plain], e.step_ahead,
                  lambda _: seen.append(hooked.done))
    assert len(plain.output_ids) == 12
    # while the hook lives every turn launches a dispatch and finishes
    # it: none ahead
    turns = seen.index(True) + 1
    assert turns >= 3 and all(d["ahead"] == 0 for d in disp[:turns])
    assert sum(d["ahead"] for d in disp) >= 5
    _idle(e)
    e.close()


# ---------------------------------------------------------------------------
# nothing stays in flight
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("how", ["step", "decode_many", "drain", "close",
                                 "fault", "generate"])
def test_nothing_stays_in_flight(models, how, monkeypatch):
    """After a few turns ahead one dispatch is in flight. Each of the
    engine's synchronous entries finishes it (its tokens reach their
    requests) before doing its own work; a ``serve.decode`` fault raised
    by the plan of the next one leaves the allocator as the last applied
    dispatch left it."""
    prompts = _prompts(models, "llama", (14, 27, 6), seed=5)
    ref = _reference(models, "llama", prompts, 12)
    e = _engine(models, "llama")
    reqs = [Request(p, max_new_tokens=12) for p in prompts]
    for r in reqs:
        e._admit(r)
    while min(len(r.output_ids) for r in reqs) < 3:
        e.step_ahead()
    assert e._inflight is not None
    had = [len(r.output_ids) for r in reqs]

    def settled():
        # applied tokens and the allocator agree: a sequence holds its
        # prompt and every emitted token but the last (whose K/V the
        # next dispatch writes)
        assert e._inflight is None
        for r in reqs:
            if not r.done:
                assert e.alloc._lens[r.seq_id] \
                    == len(r.prompt_ids) + len(r.output_ids) - 1

    if how == "step":
        e.step()
        assert [len(r.output_ids) for r in reqs] == [n + 2 for n in had]
        settled()
    elif how == "decode_many":
        e.decode_many(2)
        assert [len(r.output_ids) for r in reqs] == [n + 3 for n in had]
        settled()
    elif how == "generate":
        # another driver's batch API on an engine with work in flight
        more = e.generate([prompts[2]], max_new_tokens=4)
        assert more == [ref[2][:4]]
        settled()
    elif how == "fault":
        plan = [{"point": "serve.decode", "action": "raise",
                 "exc": "RuntimeError", "count": 1}]
        monkeypatch.setenv(faults.PLAN_ENV, json.dumps(plan))
        faults.reset()
        try:
            with pytest.raises(RuntimeError, match="serve.decode"):
                e.step_ahead()
        finally:
            monkeypatch.delenv(faults.PLAN_ENV)
            faults.reset()
        # the dispatch in flight was finished, the one the fault stopped
        # was never planned
        assert [len(r.output_ids) for r in reqs] == [n + 1 for n in had]
        settled()
    elif how == "drain":
        stats = e.drain(timeout=120.0)
        assert stats["completed"] == 3 and stats["expired"] == 0
        settled()
        e.resume_admission()
    elif how == "close":
        e.close()
        assert [len(r.output_ids) for r in reqs] == [n + 1 for n in had]
        settled()
    # and the engine goes on from there, token for token
    while any(not r.done for r in reqs):
        e.step_ahead()
    assert [list(r.output_ids) for r in reqs] == ref
    _idle(e)
    e.close()


def test_a_drain_cut_short_drops_what_is_in_flight(models):
    """A grace window that ends with requests live expires them; the
    dispatch in flight then holds stale rows only, and is finished."""
    e = _engine(models, "llama")
    reqs = [Request(p, max_new_tokens=200)
            for p in _prompts(models, "llama", (14, 27))]
    for r in reqs:
        e._admit(r)
    for _ in range(4):
        e.step_ahead()
    assert e._inflight is not None
    stats = e.drain(timeout=0.0)
    assert stats["expired"] == 2
    assert all(r.status == "deadline_exceeded" for r in reqs)
    _idle(e)
    e.close()
