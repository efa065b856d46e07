"""The serving loop's spans and counters (ISSUE 26): every dispatch is one
``serving.dispatch`` and a ``schedule``, ``build``, ``wait`` and
``apply`` sharing its ``step`` (under it where the dispatch is launched
and finished in one turn; since ISSUE 38, in a loop that runs one
dispatch ahead, the turn's span holds the ``schedule`` and ``build`` of
the NEXT dispatch before this one's ``wait`` and ``apply``); every
retired request writes one
``serving.request`` with its five stamps in order; what the spans count
is what the counters count and what the requests received."""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.cluster import ServingCluster
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.serving import LlamaServingEngine, Request
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import compile_watch as cw
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import trace as otrace
from paddle_tpu.observability import tracing as otracing
from paddle_tpu.ops import ragged_paged_attention as RPA

PHASES = ("serving.schedule", "serving.build", "serving.wait",
          "serving.apply")
PROMPTS = (5, 40, 23, 9, 30, 17)        # 40, 23, 30, 17: several chunks
NEW = 5


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256))
    m.eval()
    return m


def _engine(model, **kw):
    return LlamaServingEngine(model, max_batch=4, page_size=8, num_pages=64,
                              max_pages_per_seq=16, chunk_budget=16, **kw)


def _value(name, *labels):
    m = om.default_registry().get(name)
    if m is None:
        return 0.0
    child = m.peek(*labels) if labels else m
    return 0.0 if child is None else child.value


@pytest.fixture(scope="module")
def served(model):
    """A handful of requests through ``ServingCluster`` over one tiny
    engine. Returns (ring events, requests, counters moved)."""
    om.default_registry().clear()
    cluster = ServingCluster(lambda: _engine(model), num_replicas=1,
                             ttl=60.0).start()
    try:
        deadline = time.time() + 120
        while not cluster.ready():
            assert time.time() < deadline
            time.sleep(0.02)
        otrace.clear()
        rng = np.random.RandomState(0)
        t_before = time.perf_counter()
        with otracing.activate(otracing.mint()):
            traced = cluster.submit(rng.randint(0, 256, (12,)).tolist(),
                                    max_new_tokens=NEW)
        reqs = [cluster.submit(rng.randint(0, 256, (n,)).tolist(),
                               max_new_tokens=NEW) for n in PROMPTS]
        for r in reqs + [traced]:
            assert r.wait(180), r.status
            assert r.status == "completed"
    finally:
        cluster.stop()
    counters = {
        "dispatches": {k: _value("serving_dispatches_total", k)
                       for k in ("mixed", "decode", "scan")},
        "tokens": {k: _value("serving_dispatch_tokens_total", k)
                   for k in ("prefill", "decode", "pad")},
        "prefill_total": _value("serving_prefill_tokens_total"),
        "generated": _value("serving_generated_tokens_total"),
        "queue_wait": om.default_registry().get(
            "serving_queue_wait_seconds"),
    }
    return otrace.get_events(), reqs + [traced], counters, t_before


def _by(events, name):
    return [e for e in events if e["name"] == name]


def test_every_dispatch_has_its_four_phases_under_one_step(served):
    events = served[0]
    disp = _by(events, "serving.dispatch")
    assert len(disp) >= 8
    steps = [d["args"]["step"] for d in disp]
    assert len(set(steps)) == len(steps) and steps == sorted(steps)
    kids_of = {}
    for d in disp:
        kids = kids_of[d["args"]["step"]] = sorted(
            (e for e in events if e["name"] in PHASES
             and e["args"]["step"] == d["args"]["step"]),
            key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == list(PHASES)
        # in order, never overlapping; the span is the turn that
        # FINISHED the dispatch: its wait and apply lie inside it
        at = kids[0]["ts"]
        for k in kids:
            assert k["ts"] >= at - 1e-3
            at = k["ts"] + k["dur"]
        assert kids[2]["ts"] >= d["ts"] - 1e-3
        assert at <= d["ts"] + d["dur"] + 1e-3
        assert kids[0]["args"]["lock_wait_s"] >= 0
        assert d["args"]["kind"] in ("mixed", "decode")
        assert d["args"]["t_cap"] == (16 if d["args"]["kind"] == "mixed"
                                      else 4)
        assert 0 < d["args"]["tokens"] <= d["args"]["t_cap"]
        assert d["args"]["decode_rows"] <= d["args"]["rows"]
    for d, nxt in zip(disp, disp[1:]):
        # a dispatch enqueued while its predecessor was unapplied was
        # planned and built in the turn that finished the predecessor,
        # BEFORE that turn waited: the device had it queued
        if nxt["args"]["ahead"]:
            assert nxt["args"]["step"] == d["args"]["step"] + 1
            sched, build = kids_of[nxt["args"]["step"]][:2]
            wait = kids_of[d["args"]["step"]][2]
            assert d["ts"] - 1e-3 <= sched["ts"]
            assert build["ts"] + build["dur"] <= wait["ts"] + 1e-3
    for d in disp:
        if not d["args"]["ahead"]:
            # launched with nothing in flight: in its own turn (all
            # four phases under its span) or as the first of a run
            # ahead (planned and built the turn before)
            sched = kids_of[d["args"]["step"]][0]
            first_of_run = sched["ts"] < d["ts"] - 1e-3
            assert first_of_run or d["args"]["dev_tokens"] == 0
    # the pre-existing span stays, inside the build
    inner = _by(events, "serving.mixed_step")
    builds = _by(events, "serving.build")
    assert len(inner) == len(builds) == len(disp)
    for m, b in zip(inner, builds):
        assert b["ts"] <= m["ts"] \
            and m["ts"] + m["dur"] <= b["ts"] + b["dur"] + 1e-3


def test_every_build_says_what_it_handed_the_device(served, model):
    """``serving.build`` carries ``h2d_arrays``, the host arrays the
    step program was handed this dispatch (one staged buffer), and
    ``h2d_bytes``, that buffer's size: the layout's, by program shape."""
    events = served[0]
    engine = _engine(model)
    nbytes = {kind: engine._dispatch_layout(t_cap).nbytes
              for kind, t_cap in (("mixed", 16), ("decode", 4))}
    engine.close()
    assert nbytes["mixed"] > nbytes["decode"] > 0
    kind_of = {d["args"]["step"]: d["args"]["kind"]
               for d in _by(events, "serving.dispatch")}
    builds = _by(events, "serving.build")
    assert len(builds) == len(kind_of)
    for b in builds:
        assert b["args"]["h2d_arrays"] == 1
        assert b["args"]["h2d_bytes"] == nbytes[kind_of[b["args"]["step"]]]
    assert {kind_of[b["args"]["step"]] for b in builds} \
        == {"mixed", "decode"}


def test_ahead_counters_follow_the_requests_own_parameters(served):
    """``ahead``, ``dev_tokens`` and ``stale_rows`` of the cluster's
    loop: every request asks for NEW tokens and names no EOS, so no row
    is ever stale; a request's NEW - 1 decode rows each took their input
    token on the device exactly where their dispatch was enqueued behind
    the one that computed it; and the loop did run ahead once both
    program shapes were warm."""
    disp = [d["args"] for d in _by(served[0], "serving.dispatch")]
    assert all(d["stale_rows"] == 0 for d in disp)
    assert _value("serving_dispatch_stale_rows_total") == 0
    assert sum(d["decode_rows"] for d in disp) \
        == (NEW - 1) * (len(PROMPTS) + 1)
    for d in disp:
        assert d["ahead"] in (0, 1)
        assert d["dev_tokens"] == d["ahead"] * d["decode_rows"]
    assert sum(d["ahead"] for d in disp) >= 4
    assert sum(d["dev_tokens"] for d in disp) > 0


def test_replica_tick_wraps_each_dispatch_and_idle_turns_are_dark(served):
    events = served[0]
    ticks, disp = _by(events, "replica.tick"), _by(events,
                                                   "serving.dispatch")
    for d in disp:
        assert any(t["ts"] <= d["ts"] and d["ts"] + d["dur"]
                   <= t["ts"] + t["dur"] + 1e-3 for t in ticks)
    # a turn is recorded only where it served something: far fewer
    # than the idle turns of the 2 ms loop
    assert len(ticks) <= len(disp) + len(PROMPTS) + 3
    assert sum(t["args"]["admitted"] for t in ticks) == len(PROMPTS) + 1
    assert sum(t["args"]["reaped"] for t in ticks) == len(PROMPTS) + 1


def test_every_retired_request_has_one_span_with_stamps_in_order(served):
    events, reqs, _, t_before = served
    spans = _by(events, "serving.request")
    assert len(spans) == len(reqs)
    assert sorted(s["args"]["prompt_len"] for s in spans) \
        == sorted(list(PROMPTS) + [12])
    for s in spans:
        a = s["args"]
        assert t_before <= a["t_submit"] <= a["t_admit"] \
            <= a["t_first_chunk"] <= a["t_first_token"] <= a["t_done"]
        assert a["status"] == "completed" and a["output_len"] == NEW
        assert a["cached_tokens"] == 0
        assert otrace.to_perf_counter(s["ts"]) \
            == pytest.approx(a["t_submit"], abs=1e-6)
    # the same stamps are on the marker each request writes at its
    # first token (a request still decoding has no serving.request yet)
    marks = {m["args"]["seq_id"]: m["args"]
             for m in _by(events, "serving.first_token")}
    assert len(marks) == len(spans)
    for s in spans:
        a, m = s["args"], marks[s["args"]["seq_id"]]
        assert all(m[k] == a[k] for k in (
            "t_submit", "t_admit", "t_first_chunk", "t_first_token"))
        # the histogram's clock skips a cold compile; the stamps do not
        assert 0 < m["ttft_seconds"] \
            <= a["t_first_token"] - a["t_admit"] + 1e-5
    # the request submitted under a distributed trace carries its id
    ids = [s["args"].get("trace_id") for s in spans]
    assert sum(i is not None for i in ids) == 1
    # several chunks: the long prompts' prefill took more than one
    # dispatch, so first chunk and first token are different dispatches
    long_ = [s["args"] for s in spans if s["args"]["prompt_len"] == 40]
    assert long_[0]["t_first_token"] > long_[0]["t_first_chunk"]


def test_spans_sum_to_the_counters_and_to_what_was_received(served):
    events, reqs, c, _ = served
    disp = _by(events, "serving.dispatch")
    tokens = sum(d["args"]["tokens"] for d in disp)
    prefill = sum(d["args"]["prefill_tokens"] for d in disp)
    caps = sum(d["args"]["t_cap"] for d in disp)
    emitted = sum(e["args"]["emitted"] for e in _by(events,
                                                    "serving.apply"))
    received = sum(len(r.output_ids) for r in reqs)
    assert emitted == received == c["generated"] == NEW * len(reqs)
    assert prefill == sum(PROMPTS) + 12 == c["prefill_total"] \
        == c["tokens"]["prefill"]
    assert tokens - prefill == c["tokens"]["decode"]
    assert caps - tokens == c["tokens"]["pad"]
    kinds = [d["args"]["kind"] for d in disp]
    assert kinds.count("mixed") == c["dispatches"]["mixed"]
    assert kinds.count("decode") == c["dispatches"]["decode"]
    assert c["dispatches"]["scan"] == 0
    assert c["queue_wait"].count == len(reqs)


def test_decode_scan_has_the_same_shape(model):
    om.default_registry().clear()
    engine = _engine(model)
    reqs = [Request(list(range(1, 6)), max_new_tokens=12),
            Request(list(range(3, 12)), max_new_tokens=12)]
    otrace.clear()
    for r in reqs:
        engine.add_request(r)
    while any(not r.done for r in reqs):
        assert engine.decode_many(8) > 0
    events = otrace.get_events()
    scans = [d for d in _by(events, "serving.dispatch")
             if d["args"]["kind"] == "scan"]
    assert scans and len(_by(events, "serving.decode_scan")) == len(scans)
    for d in scans:
        kids = sorted((e for e in events if e["name"] in PHASES
                       and e["args"]["step"] == d["args"]["step"]),
                      key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == list(PHASES)
        assert d["args"]["prefill_tokens"] == 0
        assert d["args"]["tokens"] == d["args"]["rows"] \
            * (d["args"]["t_cap"] // 4)
        # the scan still takes its arrays one by one: last tokens,
        # tables, lengths and the sampler's seven
        build = kids[1]["args"]
        assert build["h2d_arrays"] == 10
        assert build["h2d_bytes"] == 4 * (4 + 4 * 16 + 4 + 5 * 4 + 2 * 4 * 8)
    assert _value("serving_dispatches_total", "scan") == len(scans)
    assert sum(e["args"]["emitted"] for e in _by(events, "serving.apply")) \
        == sum(len(r.output_ids) for r in reqs) == 24
    # no cluster: the engine's own admission is the submission
    for s in _by(events, "serving.request"):
        assert s["args"]["t_submit"] == s["args"]["t_admit"]


def test_dispatch_says_what_the_kernel_walks(model):
    """``serving.dispatch`` carries ``kv_pages``, the pages the
    scheduled rows' contexts hold (counted here from the allocator's
    own tables), and ``table_slots``, the slots of the tables the
    program is given: the share of the second that the rope-fused
    kernel's walk no longer visits."""
    om.default_registry().clear()
    engine = _engine(model)
    want = {}

    def pages(sid, kv_len):
        return len(set(engine.alloc.page_positions(sid, 0,
                                                   kv_len)[0].tolist()))

    rows_of, scan_of = engine._dispatch_rows, engine._dispatch_scan

    def spy_rows(rows, cow):
        want[engine._dispatch_count - 1] = sum(
            pages(sid, start + n) for _, sid, start, n, _, _ in rows)
        return rows_of(rows, cow)

    def spy_scan(n, live, sids, last_tok, start_lens, cow):
        want[engine._dispatch_count - 1] = sum(
            pages(sid, start_lens[sid] + 1) for sid in sids)
        return scan_of(n, live, sids, last_tok, start_lens, cow)

    engine._dispatch_rows, engine._dispatch_scan = spy_rows, spy_scan
    reqs = [Request(list(range(1, n + 1)), max_new_tokens=6)
            for n in (40, 5, 23)]
    otrace.clear()
    for r in reqs:
        engine.add_request(r)
    while any(r._prefilled < len(r.prompt_ids) for r in reqs):
        engine.step()
    engine.step()
    while any(not r.done for r in reqs):
        assert engine.decode_many(4) > 0
    disp = _by(otrace.get_events(), "serving.dispatch")
    kinds = {d["args"]["kind"] for d in disp}
    assert kinds == {"mixed", "decode", "scan"}
    for d in disp:
        a = d["args"]
        assert a["kv_pages"] == want[a["step"]] > 0
        assert a["table_slots"] == 16 * (
            engine.rows_cap if a["kind"] == "mixed" else engine.max_batch)
        assert a["kv_pages"] <= a["table_slots"]
    # three sequences of at most 46 tokens in pages of 8: the walk is
    # a small part of the tables
    assert max(d["args"]["kv_pages"] for d in disp) <= 3 * 6


@pytest.mark.parametrize("case", ["greedy", "mixed", "scan"])
def test_dispatch_says_how_many_rows_sample(model, case):
    """``serving.dispatch`` carries ``sampled_rows``, the dispatch's
    rows whose request has ``temperature > 0`` (counted here from the
    scheduled rows' own requests): 0 is the side of the sample step's
    branch that takes the argmax and nothing else."""
    om.default_registry().clear()
    engine = _engine(model)
    want = {}

    def samples(r):
        return r.sampling is not None and r.sampling.temperature > 0

    rows_of, scan_of = engine._dispatch_rows, engine._dispatch_scan

    def spy_rows(rows, cow):
        want[engine._dispatch_count - 1] = sum(
            samples(row[0]) for row in rows)
        return rows_of(rows, cow)

    def spy_scan(n, live, *rest):
        want[engine._dispatch_count - 1] = sum(samples(r) for r in live)
        return scan_of(n, live, *rest)

    engine._dispatch_rows, engine._dispatch_scan = spy_rows, spy_scan
    hot = SamplingParams(temperature=0.9, top_p=0.9, seed=5)
    # a bias is no temperature: such a row is a greedy row
    params = {"greedy": [None, SamplingParams(logit_bias={7: 3.0}), None],
              "mixed": [hot, None, hot],
              "scan": [None, hot, None]}[case]
    reqs = [Request(list(range(1, n + 1)), max_new_tokens=6, sampling=sp)
            for n, sp in zip((40, 5, 23), params)]
    otrace.clear()
    for r in reqs:
        engine.add_request(r)
    while any(not r.done for r in reqs):
        if case == "scan":
            assert engine.decode_many(4) > 0
        else:
            engine.step()
    disp = _by(otrace.get_events(), "serving.dispatch")
    assert disp and len(disp) == len(want)
    for d in disp:
        a = d["args"]
        assert a["sampled_rows"] == want[a["step"]] <= a["rows"]
    seen = {d["args"]["sampled_rows"] for d in disp}
    kinds = {d["args"]["kind"] for d in disp}
    if case == "greedy":
        assert seen == {0}
    elif case == "mixed":
        # the 40-token prompt is several rows of one dispatch, each
        # its request's; decode-only steps carry both sampling rows
        assert max(seen) >= 2 and kinds >= {"mixed", "decode"}
    else:
        # the sampling request retires first: later scans carry none
        assert {d["args"]["sampled_rows"] for d in disp
                if d["args"]["kind"] == "scan"} == {0, 1}


def test_metrics_off_records_nothing_and_serves_the_same(model,
                                                         monkeypatch):
    want = _engine(model).generate([[1, 2, 3, 4]], max_new_tokens=4)
    monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
    otrace.clear()
    got = _engine(model).generate([[1, 2, 3, 4]], max_new_tokens=4)
    assert [list(o) for o in got] == [list(o) for o in want]
    assert otrace.get_events() == []


# ---------------------------------------------------------------------------
# expert layers' counters on the dispatch span (latent-attention experts)
# ---------------------------------------------------------------------------
def _reference_routing(fam, cfg, w, ids):
    """Experts the family's plain reference routes each position of one
    sequence to, per expert layer: ``[layers, T, k]``."""
    import jax.numpy as jnp
    d, eps = fam.dims(cfg), float(cfg["rms_norm_eps"])
    x = jnp.take(w["ends"]["embed"], jnp.asarray(ids), axis=0) \
        .astype(jnp.float32)
    valid = jnp.ones((len(ids),), bool)
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        lw = w["layers"][i]
        q, k, v = fam.attn_operands(x, lw, tuple(sorted(d.items())), eps,
                                    float(cfg["rope_theta"]), None)
        x, y = fam.attn_out(x, fam.attn_block(q, k, v, 0), lw, eps, None)
        if fam.is_dense(cfg, i):
            x = x + fam.swiglu(y, lw["gate"], lw["up"], lw["down"], None)
            continue
        idx, _ = fam.route(y, lw["router"], lw["router_bias"], valid,
                           d["k"], float(cfg["routed_scaling_factor"]),
                           bool(cfg["norm_topk_prob"]))
        chosen.append(np.asarray(idx))
        x = x + fam.moe_ffn(y, lw, cfg, valid, None)
    return np.stack(chosen)


def test_dispatch_carries_the_expert_layers_counters():
    """``experts_touched`` and ``expert_rows_max`` (medians over the
    expert layers of what the step program counted) equal what the
    family's plain reference routes the dispatch's own tokens to, and
    ``latent_rows`` the rows the allocator holds for its contexts."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "perfbench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench
    from harness import family, program

    cfg = bench.load_json("perfbench", "configs", "joyai-llm-flash-l5.json")
    bench.deep_update(cfg, cfg["rehearse"])
    fam = family.load(cfg, "joyai-llm-flash-l5")
    mla = fam.build_model(cfg, "float32")
    mla.eval()
    w, _ = program.assign_weights(fam, mla, cfg, 11, "float32")
    om.default_registry().clear()
    engine = LlamaServingEngine(mla, max_batch=4, page_size=8, num_pages=65,
                                max_pages_per_seq=16, chunk_budget=16,
                                chunk_block=8)
    seen = {}
    rows_of = engine._dispatch_rows

    def spy(rows, cow):
        seen[engine._dispatch_count - 1] = [
            (r, start, n, len(engine.alloc._tables[sid]))
            for r, sid, start, n, _, _ in rows]
        return rows_of(rows, cow)

    engine._dispatch_rows = spy
    rng = np.random.default_rng(12)
    reqs = [Request(list(rng.integers(1, cfg["vocab_size"], n)),
                    max_new_tokens=5) for n in (40, 5, 23)]
    otrace.clear()
    for r in reqs:
        engine.add_request(r)
    while any(not r.done for r in reqs):
        engine.step()
    routing = {id(r): _reference_routing(
        fam, cfg, w, list(r.prompt_ids) + list(r.output_ids))
        for r in reqs}
    disp = _by(otrace.get_events(), "serving.dispatch")
    assert {d["args"]["kind"] for d in disp} == {"mixed", "decode"}
    experts = cfg["n_routed_experts"]
    for d in disp:
        a = d["args"]
        rows = seen[a["step"]]
        per_layer = []
        for layer in range(len(routing[id(reqs[0])])):
            ids = np.concatenate([
                routing[id(r)][layer, start:start + n].reshape(-1)
                for r, start, n, _ in rows])
            counts = np.bincount(ids, minlength=experts)
            per_layer.append([(counts > 0).sum(), counts.max()])
        med = np.median(np.asarray(per_layer), axis=0)
        assert a["experts_touched"] == med[0]
        assert a["expert_rows_max"] == med[1]
        assert a["latent_rows"] == sum(start + n for _, start, n, _ in rows)
        assert all(pages * 8 >= start + n for _, start, n, pages in rows)
        # no layer runs the float ragged program: its counter stays away
        assert "tile_rows" not in a
    assert max(d["args"]["experts_touched"] for d in disp) <= experts
    engine.close()


def test_a_decoder_without_expert_layers_sets_no_expert_counters(served):
    disp = _by(served[0], "serving.dispatch")
    assert disp
    for d in disp:
        assert not {"experts_touched", "expert_rows_max",
                    "latent_rows"} & set(d["args"])


def test_dispatch_counts_slots_windows_and_the_shared_pool():
    """A model whose layers keep states and windows (ISSUE 34): after
    every dispatch ``state_slots`` is the allocator's slots in use,
    ``shared_kv_pages`` its pages in use, ``window_pages`` what its
    lengths and the window give for every window layer, and
    ``window_pages_freed`` sums to the pages that fell behind the
    windows; the build hands over one buffer with a 20th field."""
    from paddle_tpu.models import SambaYForCausalLM, tiny_sambay_config

    paddle.seed(0)
    m = SambaYForCausalLM(tiny_sambay_config())     # window 16, 2 layers
    m.eval()
    engine = _engine(m, chunk_block=8)
    page, window, layers = 8, 16, 2
    seen = []
    real = engine._slot_counters

    def spy(rows):
        got = real(rows)
        with engine._lock:
            seen.append((got, engine.alloc.slots_held,
                         engine.alloc.num_pages - engine.alloc.free_pages,
                         sorted(engine.alloc._lens.values())))
        return got

    engine._slot_counters = spy
    otrace.clear()
    reqs = [Request(list(range(1, n + 1)), max_new_tokens=30)
            for n in (5, 37, 20)]
    for r in reqs:
        engine.add_request(r)
    while not all(r.done for r in reqs):
        engine.step()
    events = otrace.get_events()
    disp = _by(events, "serving.dispatch")
    assert len(disp) == len(seen) > 30
    for d, (got, slots, pages, lens) in zip(disp, seen):
        a = d["args"]
        assert {k: a[k] for k in got} == got
        assert a["state_slots"] == slots <= 3
        assert a["shared_kv_pages"] == pages
        assert a["window_pages"] == layers * sum(
            (n - 1) // page - max(n - window, 0) // page + 1 for n in lens)
    total = sum(d["args"]["window_pages_freed"] for d in disp)
    assert total == layers * sum(
        (len(r.prompt_ids) + 29 - window) // page for r in reqs)
    layout = engine._dispatch_layout(engine.chunk_budget)
    assert [f[0] for f in layout.fields][-1] == "slots"
    for b in _by(events, "serving.build"):
        assert b["args"]["h2d_arrays"] == 1
    assert engine.alloc.slots_held == 0
    engine.close()


def test_a_model_without_states_sets_no_slot_counters(served, model):
    for d in _by(served[0], "serving.dispatch"):
        assert not {"state_slots", "window_pages", "shared_kv_pages",
                    "window_pages_freed"} & set(d["args"])
    layout = _engine(model)._dispatch_layout(16)
    assert [f[0] for f in layout.fields][-1] == "prev_idx"


# ---------------------------------------------------------------------------
# the attention kernel's small tile (ISSUE 36): how often it engages, and
# that the engine compiles, registers and prewarms what it did before
GEOMETRIES = ["mistral", "hybrid"]


def _geometry(name):
    """A tiny model with a cell's attention geometry: 4 query heads a
    kv head (Mistral-7B's 32 over 8), or the hybrid family's layers
    (pairs of key heads are one head of the cache: 8 padded query heads
    over 2, states, windows, a shared pool). Returns (model, kernel
    variants its step programs hold: whole context, window, read-only)."""
    paddle.seed(0)
    if name == "mistral":
        m = LlamaForCausalLM(LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=256))
        variants = 1
    else:
        from paddle_tpu.models import SambaYForCausalLM, tiny_sambay_config
        m, variants = SambaYForCausalLM(tiny_sambay_config()), 3
    m.eval()
    return m, variants


def _serve_three(engine):
    reqs = [Request(list(range(1, n + 1)), max_new_tokens=6)
            for n in (40, 5, 23)]
    for r in reqs:
        engine.add_request(r)
    while any(not r.done for r in reqs):
        engine.step()


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_dispatch_says_how_many_rows_take_the_small_tile(geometry):
    """``serving.dispatch`` carries ``tile_rows``, the dispatch's rows
    whose query tokens fit the float ragged program's small tile (two
    tokens at 4 query heads a kv head), counted here from the scheduled
    rows' own lengths: every row of a decode-only dispatch, the decode
    rows and the short chunk tails of a mixed one."""
    om.default_registry().clear()
    m, _ = _geometry(geometry)
    engine = _engine(m, chunk_block=8)
    group, want = 4, {}
    assert RPA.small_tile(group) == 8
    rows_of = engine._dispatch_rows

    def spy_rows(rows, cow):
        want[engine._dispatch_count - 1] = sum(
            0 < n * group <= 8 for _, _, _, n, _, _ in rows)
        return rows_of(rows, cow)

    engine._dispatch_rows = spy_rows
    otrace.clear()
    _serve_three(engine)
    disp = _by(otrace.get_events(), "serving.dispatch")
    assert disp and len(disp) == len(want)
    assert {d["args"]["kind"] for d in disp} == {"mixed", "decode"}
    for d in disp:
        a = d["args"]
        assert a["tile_rows"] == want[a["step"]] <= a["rows"]
        if a["kind"] == "decode":
            assert a["tile_rows"] == a["rows"] == a["decode_rows"]
        else:
            assert a["tile_rows"] >= a["decode_rows"]
    # a mixed dispatch holds rows of both sizes
    assert any(0 < d["args"]["tile_rows"] < d["args"]["rows"]
               for d in disp if d["args"]["kind"] == "mixed")
    engine.close()


#: the fields of a dispatch's one buffer: as they were before ISSUE 36,
#: and ``prev_idx`` (ISSUE 38: where a token the device holds lies)
LAYOUT_FIELDS = ["tokens", "pos", "flat_idx", "last_idx", "tables",
                 "kv_lens", "q_starts", "q_lens", "w_starts", "w_flats",
                 "w_ends", "temps", "top_ps", "top_ks", "seeds",
                 "slot_ids", "slot_vals", "cmodes", "prev_idx"]


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_small_tile_adds_no_compiled_program(geometry, tmp_path,
                                                 monkeypatch):
    """The kernel picks a row's size from ``q_lens`` inside ONE
    program: the engine still has its two program shapes, its layout
    the same fields, the step programs one kernel a variant and shape
    (the layers share it), the shape registry the same two entries,
    and an engine of equal geometry prewarms exactly those."""
    monkeypatch.setenv("PADDLE_TPU_SHAPE_REGISTRY",
                       str(tmp_path / "serving_shapes.json"))
    monkeypatch.setattr(cw, "_shape_registry", None)
    m, variants = _geometry(geometry)
    made = {}
    make = RPA._make_fused_rope
    make.cache_clear()
    monkeypatch.setattr(
        RPA, "_make_fused_rope",
        lambda *key: made.setdefault(key, make(*key)))
    engine = _engine(m, chunk_block=8)
    assert engine._cache_dir is not None
    _serve_three(engine)
    assert sorted(engine._layouts) == [4, 16]
    assert len(engine._mixed_static._cache) == 2
    assert engine._warm_dispatches == 2 and engine.prewarmed is None
    for t_cap in (4, 16):
        fields = [f[0] for f in engine._dispatch_layout(t_cap).fields]
        assert fields == LAYOUT_FIELDS + ["slots"] * (geometry == "hybrid")
    # a kernel a variant and program shape, none for a size of tile
    assert len(made) == 2 * variants
    assert cw.shape_registry().lookup(engine._shape_key) \
        == {"mixed": [4, 16]}
    engine.close()
    other = _engine(m, chunk_block=8)
    assert other._shape_key == engine._shape_key
    assert other.prewarm() == other.prewarmed \
        == {"mixed": [4, 16], "scan": []}
    assert other._warm_dispatches == 2
    assert len(other._mixed_static._cache) == 2
    assert len(made) == 2 * variants
    other.close()


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_running_ahead_adds_no_compiled_program(geometry):
    """ISSUE 38: the token array one mixed program hands the next has
    ONE shape whichever of the two program shapes wrote it, so an engine
    that served both shapes one dispatch ahead (each shape behind the
    other, tokens taken on the device both ways) holds two compiled
    mixed programs, two layouts and its two warm-up dispatches; what
    `serving.build` says it handed over is that layout's bytes, the new
    field among them."""
    om.default_registry().clear()
    m, _ = _geometry(geometry)
    engine = _engine(m, chunk_block=8)
    engine.prewarm(mixed=[16, 4])
    reqs = [Request(list(range(1, n + 1)), max_new_tokens=12)
            for n in (40, 5, 23)]
    otrace.clear()
    for r in reqs[:2]:
        engine._admit(r)
    turns = 0
    while any(not r.done for r in reqs):
        engine.step_ahead()
        turns += 1
        if turns == 9:      # decode-only dispatches, then chunks again
            engine._admit(reqs[2])
        assert turns < 200
    events = otrace.get_events()
    disp = [d["args"] for d in _by(events, "serving.dispatch")]
    ahead = [d["kind"] for d in disp if d["ahead"] and d["dev_tokens"]]
    assert {"mixed", "decode"} <= set(ahead)
    kinds = [d["kind"] for d in disp]
    assert any(a != b for a, b in zip(kinds, kinds[1:]))
    assert sorted(engine._layouts) == [4, 16]
    assert len(engine._mixed_static._cache) == 2
    assert engine._warm_dispatches == 2
    assert engine._carry._data.shape == (engine.rows_cap,)
    kind_of = {d["step"]: d["kind"] for d in disp}
    for b in _by(events, "serving.build"):
        lay = engine._dispatch_layout(
            16 if kind_of[b["args"]["step"]] == "mixed" else 4)
        assert b["args"]["h2d_bytes"] == lay.nbytes == 4 * sum(
            end - at for _, at, end, _, _ in lay.fields)
        assert [f[0] for f in lay.fields] \
            == LAYOUT_FIELDS + ["slots"] * (geometry == "hybrid")
    engine.close()
