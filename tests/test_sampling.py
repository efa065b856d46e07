"""Per-request sampling tests (ROADMAP item 4, the front door's engine
half).

Two gates from the issue:

- **Greedy stays bitwise.** An engine with the sample step compiled in
  (``sampling=True``, the default) must emit exactly what the
  pre-sampling program (``sampling=False``) emits for greedy rows —
  token-for-token, including mixed batches where greedy and sampled
  rows share one dispatch.
- **Distribution exactness.** The speculative engine's SAMPLED outputs
  equal the non-speculative engine's with the same seed, across a
  temperature/top-p grid: the rejection-sampling verify (accept draft
  w.p. p(draft), resample residual on reject — implemented by the
  position-keyed sample, see sampling.py) must not change the law OR
  the realized draw of any sequence.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.sampling import (GREEDY, SamplingParams,
                                           sampled_next_tokens)
from paddle_tpu.inference.serving import LlamaServingEngine, Request
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config())
    m.eval()
    return m


def _make_engine(model, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 48)
    # no prefix cache: page-accounting asserts below expect completed
    # requests to return the pool to exactly num_pages
    kw.setdefault("prefix_cache", False)
    return LlamaServingEngine(model, **kw)


def _run(engine, prompt, n, sampling=None, stop=()):
    r = Request(prompt, max_new_tokens=n, sampling=sampling, stop=stop)
    engine.add_request(r)
    while not r.done:
        engine.step()
    return r


# ---------------------------------------------------------------------------
# SamplingParams validation
# ---------------------------------------------------------------------------
def test_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(temperature=float("nan"))
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(seed=2 ** 31)
    with pytest.raises(ValueError):
        SamplingParams(logit_bias={3: float("inf")})
    with pytest.raises(ValueError):
        SamplingParams(constraint=42)
    assert GREEDY.is_greedy
    assert not SamplingParams(temperature=0.7).is_greedy


def test_params_spec_roundtrip():
    p = SamplingParams(temperature=0.7, top_p=0.9, top_k=5, seed=11,
                      stop=(3, 4), logit_bias={7: -1.5})
    q = SamplingParams.from_spec(p.to_spec())
    assert (q.temperature, q.top_p, q.top_k, q.seed) == (0.7, 0.9, 5, 11)
    assert q.stop == (3, 4) and q.logit_bias == {7: -1.5}
    with pytest.raises(ValueError):
        SamplingParams(constraint=lambda p, o: None).to_spec()
    assert SamplingParams.from_spec(None) is None


def test_request_rejects_non_params():
    with pytest.raises(ValueError):
        Request([1, 2], sampling={"temperature": 1.0})


# ---------------------------------------------------------------------------
# the vectorized sample step (pure-jax unit tests)
# ---------------------------------------------------------------------------
def _step_args(n, v, **over):
    import jax.numpy as jnp

    args = {
        "temps": np.zeros((n,), np.float32),
        "top_ps": np.ones((n,), np.float32),
        "top_ks": np.zeros((n,), np.int32),
        "seeds": np.zeros((n,), np.int32),
        "positions": np.arange(n, dtype=np.int32),
        "slot_ids": np.full((n, 4), -1, np.int32),
        "slot_vals": np.zeros((n, 4), np.float32),
        "cmodes": np.zeros((n,), np.int32),
    }
    args.update(over)
    return {k: jnp.asarray(a) for k, a in args.items()}


def test_sample_step_greedy_is_argmax():
    rng = np.random.RandomState(0)
    logits = rng.randn(5, 33).astype(np.float32)
    import jax.numpy as jnp

    out = sampled_next_tokens(jnp.asarray(logits), **_step_args(5, 33))
    assert np.array_equal(np.asarray(out), logits.argmax(-1))


def test_sample_step_top_k_one_is_argmax():
    """temperature > 0 with top_k=1 keeps only the argmax token."""
    rng = np.random.RandomState(1)
    logits = rng.randn(4, 17).astype(np.float32)
    import jax.numpy as jnp

    out = sampled_next_tokens(
        jnp.asarray(logits),
        **_step_args(4, 17, temps=np.full((4,), 1.3, np.float32),
                     top_ks=np.ones((4,), np.int32),
                     seeds=np.arange(4, dtype=np.int32)))
    assert np.array_equal(np.asarray(out), logits.argmax(-1))


def test_sample_step_top_p_tiny_is_argmax():
    """A nucleus smaller than the top token's mass keeps only it."""
    rng = np.random.RandomState(2)
    logits = rng.randn(4, 17).astype(np.float32)
    import jax.numpy as jnp

    out = sampled_next_tokens(
        jnp.asarray(logits),
        **_step_args(4, 17, temps=np.full((4,), 1.0, np.float32),
                     top_ps=np.full((4,), 1e-6, np.float32),
                     seeds=np.arange(4, dtype=np.int32)))
    assert np.array_equal(np.asarray(out), logits.argmax(-1))


def test_sample_step_counter_key_determinism():
    """The draw is a pure function of (seed, position) — batch
    composition and row order don't matter."""
    rng = np.random.RandomState(3)
    logits = rng.randn(6, 29).astype(np.float32)
    import jax.numpy as jnp

    kw = dict(temps=np.full((6,), 1.1, np.float32),
              seeds=np.arange(6, dtype=np.int32),
              positions=np.arange(6, dtype=np.int32) * 3)
    a = np.asarray(sampled_next_tokens(jnp.asarray(logits),
                                       **_step_args(6, 29, **kw)))
    # same rows, reversed packing
    perm = np.arange(6)[::-1].copy()
    kw2 = {k: np.ascontiguousarray(v[perm]) for k, v in kw.items()}
    b = np.asarray(sampled_next_tokens(jnp.asarray(logits[perm]),
                                       **_step_args(6, 29, **kw2)))
    assert np.array_equal(a[perm], b)


def test_sample_step_constraint_mask():
    """Constraint rows sample only from their allowed slot ids."""
    rng = np.random.RandomState(4)
    logits = rng.randn(3, 50).astype(np.float32)
    slot_ids = np.full((3, 4), -1, np.int32)
    slot_ids[0, :2] = [7, 9]
    slot_ids[2, :3] = [1, 2, 3]
    import jax.numpy as jnp

    out = np.asarray(sampled_next_tokens(
        jnp.asarray(logits),
        **_step_args(3, 50, temps=np.full((3,), 1.5, np.float32),
                     seeds=np.arange(3, dtype=np.int32),
                     slot_ids=slot_ids,
                     cmodes=np.array([1, 0, 1], np.int32))))
    assert out[0] in (7, 9)
    assert out[2] in (1, 2, 3)


# ---------------------------------------------------------------------------
# the branch the device takes from ``temps`` (PR 33): bitwise the
# straight line it replaced, and the sort lives under the conditional
# ---------------------------------------------------------------------------
def _frozen_sampled_next_tokens(logits, temps, top_ps, top_ks, seeds,
                                positions, slot_ids, slot_vals, cmodes):
    """``sampled_next_tokens`` as it stood before PR 33, frozen: the
    sampled values of every row computed in a straight line and
    selected at the end. The reference of the parity test below."""
    import jax
    import jax.numpy as jnp

    masked = -1e30
    n, v = logits.shape
    l = logits.astype(jnp.float32)
    rows = jnp.arange(n, dtype=jnp.int32)
    l = l.at[rows[:, None], jnp.clip(slot_ids, 0, v - 1)].add(slot_vals)
    tok = jnp.arange(v, dtype=jnp.int32)[None, None, :]
    allowed = jnp.any((slot_ids[:, :, None] == tok)
                      & (slot_ids[:, :, None] >= 0), axis=1)
    l = jnp.where((cmodes[:, None] == 1) & ~allowed, masked, l)
    greedy = jnp.argmax(l, axis=-1)
    ls = l / jnp.maximum(temps, 1e-6)[:, None]
    sl = jnp.sort(ls, axis=-1)[:, ::-1]
    kk = jnp.where(top_ks > 0, jnp.minimum(top_ks, v), v)
    kth = jnp.take_along_axis(sl, (kk - 1)[:, None], axis=1)
    sp = jax.nn.softmax(sl, axis=-1)
    cum_before = jnp.cumsum(sp, axis=-1) - sp
    n_keep = jnp.maximum(
        jnp.sum(cum_before < top_ps[:, None], axis=-1), 1)
    pth = jnp.take_along_axis(sl, (n_keep - 1)[:, None], axis=1)
    keep = ls >= jnp.maximum(kth, pth)

    def _gumbel(seed, pos):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.gumbel(key, (v,), dtype=jnp.float32)

    g = jax.vmap(_gumbel)(seeds, positions)
    z = jnp.where(keep, ls + g, -jnp.inf)
    sampled = jnp.argmax(z, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int64)


_N, _V = 6, 257


def _branch_case(name):
    """Row parameters of one case of the parity test, [_N] rows over
    a vocabulary of _V."""
    temps = np.zeros((_N,), np.float32)
    over = {"seeds": np.arange(_N, dtype=np.int32) * 7 + 3,
            "positions": np.arange(_N, dtype=np.int32) * 5 + 11}
    slot_ids = np.full((_N, 4), -1, np.int32)
    slot_vals = np.zeros((_N, 4), np.float32)
    if name == "one_sampling_row":
        temps[2] = 0.8
    elif name == "all_sampling":
        temps[:] = np.linspace(0.3, 1.7, _N)
    elif name == "greedy_bias":
        slot_ids[1, :2] = [5, 200]
        slot_vals[1, :2] = [40.0, -40.0]
        slot_ids[4, 0] = 17
        slot_vals[4, 0] = 25.0
    elif name == "greedy_constraint":
        slot_ids[0, :3] = [9, 10, 250]
        slot_ids[3, :2] = [1, 2]
        over["cmodes"] = np.array([1, 0, 0, 1, 0, 0], np.int32)
    elif name == "top_k_top_p":
        temps[:] = 1.0
        temps[5] = 0.0                      # a greedy row beside them
        over["top_ks"] = np.array([3, 0, 40, 0, 1, 0], np.int32)
        over["top_ps"] = np.array([1.0, 0.5, 0.9, 0.05, 1.0, 1.0],
                                  np.float32)
    else:
        assert name == "all_greedy"
    return dict(over, temps=temps, slot_ids=slot_ids,
                slot_vals=slot_vals)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "all_greedy", "one_sampling_row", "all_sampling", "greedy_bias",
    "greedy_constraint", "top_k_top_p"])
def test_sample_step_branch_bitwise_vs_straight_line(case, dtype):
    """Whichever side of the branch a batch takes, every row's token
    is the one the straight-line sample step gave it."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(33)
    logits = jnp.asarray(rng.randn(_N, _V).astype(np.float32) * 3.0,
                         dtype=dtype)
    args = _step_args(_N, _V, **_branch_case(case))
    got = jax.jit(sampled_next_tokens)(logits, **args)
    want = jax.jit(_frozen_sampled_next_tokens)(logits, **args)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if case == "one_sampling_row":
        # the greedy rows beside a sampling row keep the all-greedy
        # batch's tokens
        alone = jax.jit(sampled_next_tokens)(
            logits, **_step_args(_N, _V, **_branch_case("all_greedy")))
        keep = np.asarray(args["temps"]) == 0
        assert np.array_equal(np.asarray(got)[keep],
                              np.asarray(alone)[keep])


def _hlo_computations(text):
    """{name: (body lines, names it calls through anything but a
    conditional's branches, names a conditional in it branches to)}
    of a compiled module's text."""
    import re

    comps, name, lines = {}, None, []
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if m and name is None:
            name, lines = m.group(1), []
        elif line.startswith("}") and name is not None:
            comps[name] = lines
            name = None
        elif name is not None:
            lines.append(line)
    out, names = {}, set(comps)
    for cname, body in comps.items():
        calls, branches = set(), set()
        for line in body:
            refs = set(re.findall(r"%([\w.\-]+)", line)) & names
            if re.search(r"\bconditional\(", line):
                branches |= refs
            else:
                calls |= refs
        out[cname] = (body, calls, branches)
    return out


def _sorts_by_side(fn):
    """(has a conditional, sort instructions the compiled ``fn`` can
    reach without entering a conditional's branch, those it reaches
    only through one)."""
    import re

    import jax
    import jax.numpy as jnp

    text = jax.jit(fn).lower(
        jnp.zeros((_N, _V), jnp.float32),
        **_step_args(_N, _V)).compile().as_text()
    comps = _hlo_computations(text)
    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M).group(1)

    def reach(through_branches):
        seen, todo = set(), [entry]
        while todo:
            c = todo.pop()
            if c in seen:
                continue
            seen.add(c)
            _, calls, branches = comps[c]
            todo += calls | (branches if through_branches else set())
        return seen

    def sorts(names):
        return [ln for c in names for ln in comps[c][0]
                if re.search(r"\bsort\(", ln)]

    outside = reach(False)
    return (any(comps[c][2] for c in outside), sorts(outside),
            sorts(reach(True) - outside))


def test_sample_step_sort_lives_under_the_conditional():
    """The compiled sample step holds a ``conditional``, and its
    vocabulary sort is reachable only through that conditional's
    branch computations: a batch in which no row samples cannot run
    it. The frozen straight line, read the same way, sorts outside."""
    has_cond, outside, inside = _sorts_by_side(sampled_next_tokens)
    assert has_cond and not outside and inside
    has_cond, outside, inside = _sorts_by_side(
        _frozen_sampled_next_tokens)
    assert not has_cond and outside and not inside


# ---------------------------------------------------------------------------
# greedy stays bitwise against the pre-sampling program
# ---------------------------------------------------------------------------
def test_greedy_bitwise_vs_sampling_off(model):
    rng = np.random.RandomState(0)
    v = model.config.vocab_size
    prompts = [rng.randint(0, v, (n,)).tolist() for n in (5, 9, 3)]
    off = _make_engine(model, sampling=False)
    on = _make_engine(model, sampling=True)
    want = off.generate(prompts, max_new_tokens=6)
    got = on.generate(prompts, max_new_tokens=6)
    assert got == want


def test_greedy_row_unchanged_next_to_sampled_row(model):
    """A greedy request sharing dispatches with a sampled one emits
    exactly its solo-greedy continuation."""
    rng = np.random.RandomState(5)
    v = model.config.vocab_size
    pg = rng.randint(0, v, (6,)).tolist()
    ps = rng.randint(0, v, (4,)).tolist()
    e0 = _make_engine(model, sampling=False)
    want = e0.generate([pg], max_new_tokens=8)[0]

    e = _make_engine(model)
    rg = Request(pg, max_new_tokens=8)
    rs = Request(ps, max_new_tokens=8,
                 sampling=SamplingParams(temperature=1.2, seed=7))
    e.add_request(rg)
    e.add_request(rs)
    while not (rg.done and rs.done):
        e.step()
    assert rg.output_ids == want


# ---------------------------------------------------------------------------
# seeded sampling semantics
# ---------------------------------------------------------------------------
def test_same_seed_same_sequence(model):
    rng = np.random.RandomState(6)
    p = rng.randint(0, model.config.vocab_size, (5,)).tolist()
    e = _make_engine(model)
    sp = SamplingParams(temperature=1.0, seed=42)
    a = _run(e, p, 8, sampling=sp).output_ids
    b = _run(e, p, 8, sampling=sp).output_ids
    assert a == b


def test_auto_seed_recorded_and_reproducible(model):
    """seed=None gets an engine-assigned seed recorded on the request;
    replaying with that seed redraws the identical sequence."""
    rng = np.random.RandomState(7)
    p = rng.randint(0, model.config.vocab_size, (5,)).tolist()
    e = _make_engine(model)
    r = _run(e, p, 8, sampling=SamplingParams(temperature=1.0))
    assert r._seed is not None
    replay = _run(e, p, 8, sampling=SamplingParams(temperature=1.0,
                                                   seed=r._seed))
    assert replay.output_ids == r.output_ids


def test_sampled_engine_rejects_when_disabled(model):
    e = _make_engine(model, sampling=False)
    with pytest.raises(ValueError, match="sampling=False"):
        _run(e, [1, 2, 3], 4,
             sampling=SamplingParams(temperature=1.0, seed=1))


def test_scan_matches_per_step(model):
    """decode_many's scan ticks draw the same randomness the per-step
    path would (the fold position rides the length carry)."""
    rng = np.random.RandomState(8)
    p = rng.randint(0, model.config.vocab_size, (5,)).tolist()
    sp = SamplingParams(temperature=1.0, top_p=0.95, seed=123)
    e = _make_engine(model)
    want = _run(e, p, 10, sampling=sp).output_ids   # per-step loop

    r = Request(p, max_new_tokens=10, sampling=sp)
    e.add_request(r)
    while r._prefilled < len(r.prompt_ids):
        e.step()
    e.decode_many(9, exact=False)                    # scan the rest
    while not r.done:
        e.step()
    assert r.output_ids == want


# ---------------------------------------------------------------------------
# the distribution-exactness gate: speculation must not change the draw
# ---------------------------------------------------------------------------
def test_distribution_exactness_spec_vs_nonspec(model):
    """Fixed-seed equality of sampled outputs for spec_k=0 vs spec_k>0
    across a temperature/top-p grid (the issue's acceptance gate)."""
    rng = np.random.RandomState(9)
    v = model.config.vocab_size
    # a self-repeating prompt so the n-gram drafter actually proposes
    base = rng.randint(0, v, (4,)).tolist()
    prompt = base * 3
    e0 = _make_engine(model, spec_k=0)
    e3 = _make_engine(model, spec_k=3)
    grid = [(0.0, 1.0), (0.7, 1.0), (1.0, 0.9), (1.3, 0.8)]
    for i, (temp, top_p) in enumerate(grid):
        sp = SamplingParams(temperature=temp, top_p=top_p,
                            seed=1000 + i)
        a = _run(e0, prompt, 12, sampling=sp)
        b = _run(e3, prompt, 12, sampling=sp)
        assert a.output_ids == b.output_ids, \
            f"spec divergence at temperature={temp}, top_p={top_p}"
        assert a.status == b.status == "completed"


def test_spec_greedy_still_token_exact(model):
    """The greedy speculation gate from PR 9 survives the generalized
    verify rule."""
    rng = np.random.RandomState(10)
    v = model.config.vocab_size
    prompt = (rng.randint(0, v, (4,)).tolist()) * 3
    e0 = _make_engine(model, spec_k=0, sampling=False)
    e3 = _make_engine(model, spec_k=3)
    a = _run(e0, prompt, 12)
    b = _run(e3, prompt, 12)
    assert a.output_ids == b.output_ids


# ---------------------------------------------------------------------------
# stop tokens at the emit boundary (satellite)
# ---------------------------------------------------------------------------
def test_stop_token_excluded_and_completed(model):
    rng = np.random.RandomState(11)
    p = rng.randint(0, model.config.vocab_size, (6,)).tolist()
    ref = _make_engine(model).generate([p], max_new_tokens=8)[0]
    stop_tok = ref[3]
    e = _make_engine(model)
    r = _run(e, p, 8, stop=[stop_tok])
    assert r.status == "completed"
    assert r.output_ids == ref[:ref.index(stop_tok)]
    assert stop_tok not in r.output_ids
    assert not e._live and e.alloc.free_pages == e.alloc.num_pages


def test_stop_tokens_merge_from_sampling_params(model):
    rng = np.random.RandomState(12)
    p = rng.randint(0, model.config.vocab_size, (6,)).tolist()
    ref = _make_engine(model).generate([p], max_new_tokens=8)[0]
    e = _make_engine(model)
    r = _run(e, p, 8, sampling=SamplingParams(stop=(ref[2],)))
    assert r.output_ids == ref[:ref.index(ref[2])]


def test_stop_token_with_speculation(model):
    """A stop token inside an accepted draft window still retires the
    request with the stop excluded (emission checks run per token)."""
    rng = np.random.RandomState(13)
    v = model.config.vocab_size
    prompt = (rng.randint(0, v, (4,)).tolist()) * 3
    ref = _make_engine(model).generate([prompt], max_new_tokens=10)[0]
    stop_tok = ref[5]
    e = _make_engine(model, spec_k=3)
    r = _run(e, prompt, 10, stop=[stop_tok])
    assert r.status == "completed"
    assert r.output_ids == ref[:ref.index(stop_tok)]
    assert not e._live and e.alloc.free_pages == e.alloc.num_pages


# ---------------------------------------------------------------------------
# logit bias + constraint hook (structured decoding)
# ---------------------------------------------------------------------------
def test_logit_bias_forces_token(model):
    rng = np.random.RandomState(14)
    p = rng.randint(0, model.config.vocab_size, (5,)).tolist()
    e = _make_engine(model)
    r = _run(e, p, 4, sampling=SamplingParams(logit_bias={3: 1e9}))
    assert r.output_ids == [3, 3, 3, 3]


def test_constraint_hook_restricts_outputs(model):
    rng = np.random.RandomState(15)
    p = rng.randint(0, model.config.vocab_size, (5,)).tolist()
    allowed = [2, 5, 8]
    calls = []

    def constraint(prompt_ids, output_ids):
        calls.append(len(output_ids))
        return allowed

    e = _make_engine(model)
    r = _run(e, p, 5,
             sampling=SamplingParams(temperature=1.0, seed=3,
                                     constraint=constraint))
    assert r.status == "completed"
    assert all(t in allowed for t in r.output_ids)
    assert calls  # the hook actually ran (host-side, per step)


def test_constraint_hook_raise_degrades_unconstrained(model):
    rng = np.random.RandomState(16)
    p = rng.randint(0, model.config.vocab_size, (5,)).tolist()
    want = _make_engine(model).generate([p], max_new_tokens=4)[0]

    def bad_hook(prompt_ids, output_ids):
        raise RuntimeError("boom")

    e = _make_engine(model)
    r = _run(e, p, 4, sampling=SamplingParams(constraint=bad_hook))
    assert r.status == "completed"
    assert r.output_ids == want   # greedy, unconstrained fallback


def test_bias_wider_than_slots_rejected(model):
    e = _make_engine(model, sample_slots=2)
    with pytest.raises(ValueError, match="sample_slots"):
        _run(e, [1, 2, 3], 2,
             sampling=SamplingParams(logit_bias={1: 1., 2: 1., 3: 1.}))
