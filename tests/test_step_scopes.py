"""ISSUE 39: the serving step's device time by model part.

Every op the engine and its layers issue lies under a named scope of
``observability.trace.SCOPES`` (the ``tf_op`` stat a profiler trace shows
for it); the scopes are metadata alone, the compiled program the same
instruction for instruction; the four per-layer readers of the benchmark
that read them; and the engine's wait for work as one ``replica.idle``
span a stretch."""

import contextlib
import functools
import glob
import os
import re
import sys
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.cluster import ServingCluster
from paddle_tpu.inference.serving import LlamaServingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import trace as otrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "perfbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench                       # noqa: E402
from harness import family                # noqa: E402

SCOPES = set(otrace.SCOPES.values())
#: the op kinds that do the work of a step: each must carry a scope
KINDS = ("dot", "convolution", "custom-call", "gather", "scatter",
         "dynamic-slice", "dynamic-update-slice", "transpose", "reduce")
_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][a-z0-9\-]*)\(")
_NAME = re.compile(r'op_name="([^"]*)"')
_PART = re.compile(r"paddle_tpu\.([a-z0-9]+(?:_[a-z0-9]+)*)")

#: the scopes each served family's step program holds
FAMILIES = {
    "mistral-7b-v0.3-l16": {"embed", "head", "attn", "attn_proj",
                            "attn_full", "attn_pack", "mlp"},
    "joyai-llm-flash-l5": {"embed", "head", "attn", "attn_proj",
                           "attn_pack", "mlp", "moe"},
    "phi-4-mini-flash-reasoning": {"embed", "head", "norm", "ssm",
                                   "ssm_scan", "gmu", "diff_attn",
                                   "attn_pack", "mlp"},
    "kimi-linear-48b-a3b-ep16": {"embed", "head", "norm", "kda",
                                 "kda_scan", "attn", "attn_proj",
                                 "attn_pack", "mlp", "moe"},
    "mellum2-12b-a2.5b-l8": {"embed", "head", "attn", "attn_proj",
                             "attn_window", "attn_full", "attn_pack", "mlp",
                             "moe"},
    "k-exaone-236b-a23b-ep16-l8": {"embed", "head", "attn", "attn_proj",
                                   "attn_window", "attn_full", "attn_pack",
                                   "qk_norm", "mlp", "moe", "mtp"},
}
#: the engine options a family's cell serves with, where not the defaults
ENGINE = {"k-exaone-236b-a23b-ep16-l8": dict(spec_k=1)}


def _kernel_names():
    """Every ``pallas_call(name=)`` of the program: in interpret mode a
    kernel's ops lie under its name as under a scope."""
    names = set()
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            names |= set(re.findall(r'"(paddle_tpu\.[a-z0-9_]+)"',
                                    f.read()))
    return names - SCOPES


@pytest.fixture(scope="module")
def fresh_compiles():
    """Compile every program anew: the persistent cache keys a program
    by its instructions alone, so an executable cached before a scope
    existed would come back with the old names."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _programs(name):
    """The optimized text of a served family's two mixed step programs
    (the chunk-budget and the decode-only shape) at its rehearsal size."""
    cfg = bench.load_json("perfbench", "configs", name + ".json")
    bench.deep_update(cfg, cfg["rehearse"])
    fam = family.load(cfg, name)
    paddle.seed(0)
    model = fam.build_model(cfg, "float32")
    model.eval()
    engine = LlamaServingEngine(model, max_batch=4, page_size=8,
                                num_pages=65, max_pages_per_seq=16,
                                chunk_budget=32, chunk_block=8,
                                **ENGINE.get(name, {}))
    engine.prewarm(mixed=[32, 4])
    texts = [c.as_text() for c in engine._mixed_static._aot.values()]
    engine.close()
    assert len(texts) == 2
    return texts


@functools.lru_cache(maxsize=None)
def _scoped_programs(name):
    """`_programs` as the program names its ops, compiled once a worker
    for both tests of a family."""
    return tuple(_programs(name))


def _ops(text):
    """``(opcode, op_name or None)`` of every instruction."""
    for line in text.splitlines():
        m = _OP.match(line)
        if m:
            n = _NAME.search(line)
            yield m.group(1), n.group(1) if n else None


def canonical(text):
    """An optimized module's text without what names its ops: the
    metadata, the source tables, and every instruction's, parameter's
    and computation's name, replaced by its order of first appearance."""
    lines, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip:
            skip = bool(line.strip())
        else:
            lines.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    body = "\n".join(lines)
    names = {}
    for m in re.finditer(r"(?:^\s*(?:ROOT |ENTRY )?%([\w.\-]+)"
                         r"|[(,] ?([\w.\-]+): )", body, re.M):
        names.setdefault(m.group(1) or m.group(2), f"v{len(names)}")
    return re.sub(r"(?<![\w.\-])%?([\w.\-]+)",
                  lambda m: names.get(m.group(1), m.group(0)), body)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_every_op_of_the_step_lies_under_a_declared_scope(name,
                                                          fresh_compiles):
    """Each op JAX emits (its name stack starts at the jitted step) of
    the kinds that do a step's work lies under a declared scope; only
    declared scopes and kernel names occur, and the family's program
    holds the scopes of its parts. Ops XLA makes itself (no metadata, or
    a parameter's name) are PERF.md's unscoped remainder."""
    kernels = _kernel_names()
    for text in _scoped_programs(name):
        seen, bare = set(), []
        for op, op_name in _ops(text):
            if op_name is None:
                continue
            parts = {"paddle_tpu." + p for p in _PART.findall(op_name)}
            assert parts <= SCOPES | kernels, op_name
            seen |= {p.split(".", 1)[1] for p in parts & SCOPES}
            if op in KINDS and op_name.startswith("jit(") \
                    and not parts & SCOPES:
                bare.append((op, op_name))
        assert not bare, bare[:10]
        assert seen == FAMILIES[name]


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_scopes_change_no_instruction(name, fresh_compiles, monkeypatch):
    """The step programs compiled with every named scope made a no-op
    are the same programs once names are taken out: a scope costs
    nothing, traced or not."""
    scoped = _scoped_programs(name)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _: contextlib.nullcontext())
    bare = _programs(name)
    assert not any("paddle_tpu.mlp" in t for t in bare)
    assert [canonical(t) for t in scoped] == [canonical(t) for t in bare]
    # names out, the two shapes are still two programs
    assert canonical(scoped[0]) != canonical(scoped[1])


def test_no_scope_name_matches_another_readers_pattern():
    """A reader of the benchmark that reads scopes reads the ops of the
    names its pattern spells out and of no other declared name
    (``attn`` is not ``diff_attn``, nothing new reads as ``moe``)."""
    for m in bench.load_json("BENCHMARK.json")["per_layer"]:
        mod = bench.load_reader(m["name"])
        for attr in ("SCOPE", "PATTERN", "GEMM"):
            pat = getattr(mod, attr, None)
            if not isinstance(pat, str) or "{" in pat:
                continue
            hit = {s.split(".", 1)[1] for s in SCOPES
                   if re.search(pat, f"jit(pure_step)/{s}/dot_general")}
            stray = {s for s in hit if not re.search(rf"\b{s}\b", pat)}
            assert not stray, (m["name"], attr, stray)


# ---------------------------------------------------------------------------
# the readers, on a synthetic trace
# ---------------------------------------------------------------------------
STEP = "jit(pure_step)/"
#: [HLO text, start ns, duration ns, scope] of two steps' ops
OPS = [
    ["%fusion.1 = bf16[8,4096] fusion(%a)", 0, 300,
     STEP + "paddle_tpu.attn/paddle_tpu.attn_proj/dot_general"],
    ["%copy.2 = f32[8,32] copy(%b)", 300, 100,
     STEP + "paddle_tpu.attn/paddle_tpu.attn_pack/transpose"],
    ["%paddle_tpu.ragged_attn_fused_rope.3 = custom-call(%c)", 400, 200,
     STEP + "paddle_tpu.attn/jit(call)"],
    ["%fusion.4 = bf16[8,4096] fusion(%d)", 600, 250,
     STEP + "paddle_tpu.mlp/dot_general"],
    ["%fusion.5 = s32[8] fusion(%e)", 850, 50,
     STEP + "paddle_tpu.head/argmax"],
    ["%copy-done.6 = bf16[4096] copy-done(%f)", 900, 100, ""],
    ["%paddle_tpu.kda_step.7 = custom-call(%g)", 1000, 100, ""],
    ["%fusion.8 = bf16[8,4096] fusion(%h)", 1100, 300,
     STEP + "paddle_tpu.moe/dot_general"],
]


class _Run:
    def __init__(self, ops, steps=2, window=(0, 2000)):
        trace = None
        if ops is not None:
            mods = [["jit_pure_step(7)", i * 1000, 900]
                    for i in range(steps)]
            trace = {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": mods},
                {"name": "XLA Ops", "events": [o[:3] for o in ops]}]}]}
        self.facts = {"trace": trace, "window_ns": window if ops is not
                      None else None, "step_pattern": r"^jit_pure_step",
                      "device_ops": ops or []}
        self.notes = []
        self.trace_dir = None

    def note(self, **kw):
        self.notes.append(kw)


def _read(name, run):
    return bench.load_reader(name).read(run)


def test_unscoped_share_counts_what_no_scope_or_kernel_names():
    run = _Run(OPS)
    # only the copy-done is anonymous: 100 of the 1,400 ns
    assert _read("unscoped_share.gap", run) == pytest.approx(100 / 14)
    (table,) = [n for n in run.notes if n.get("table")
                == "device_ms_by_scope"]
    assert table["steps"] == 2
    # a part is its scopes outermost first, then its kernel: the parts
    # of one outermost scope add up to that scope's time
    assert table["attn/attn_proj"] == pytest.approx(300 / 1e6 / 2)
    assert table["attn/ragged_attn_fused_rope"] == pytest.approx(1e-4)
    assert sum(v for k, v in table.items() if k.startswith("attn/")) \
        == pytest.approx(600 / 1e6 / 2)
    assert table["kda_step"] == table["unscoped"] == pytest.approx(5e-5)
    assert set(table) == {"table", "steps", "attn/attn_proj",
                          "attn/attn_pack", "attn/ragged_attn_fused_rope",
                          "mlp", "head", "moe", "kda_step", "unscoped",
                          "unscoped_by_op"}
    assert table["unscoped_by_op"] == {"%copy-done": pytest.approx(5e-5)}


def test_unscoped_share_counts_a_busy_instant_once():
    """A loop's body inside its ``while`` and a copy beside compute:
    each instant belongs to the innermost op (the one that began last,
    of two that began together the shorter), so nothing is counted twice
    and the parts sum to the device's busy time."""
    mod = bench.load_reader("unscoped_share.tok")
    ops = [["%while.1 = (s32[]) while(%a)", 0, 600, ""],
           ["%fusion.2 = f32[8] fusion(%b)", 100, 300,
            STEP + "paddle_tpu.kda/paddle_tpu.kda_scan/dot_general"],
           ["%copy-done.3 = f32[8] copy-done(%c)", 500, 200, ""],
           ["%fusion.4 = f32[8] fusion(%d)", 500, 100,
            STEP + "paddle_tpu.mlp/dot_general"]]
    by_op = {}
    acc = mod.by_part(ops, (0, 1000), by_op)
    assert acc == {"unscoped": 300, "kda/kda_scan": 300, "mlp": 100}
    assert by_op == {"%while": 200, "%copy-done": 100}
    assert mod.share(acc) == pytest.approx(300 / 7)


def test_unscoped_share_keeps_to_the_window():
    # the window [850, 1000) holds the head's 50 ns and the copy's 100
    run = _Run(OPS, steps=1, window=(850, 1000))
    assert _read("unscoped_share.tok", run) == pytest.approx(100 / 1.5)


@pytest.mark.parametrize("name, ms", [
    ("attn_pack_ms.gap", 100 / 2e6), ("attn_pack_ms.tok", 100 / 2e6),
    ("dense_ms.gap", 550 / 2e6), ("dense_ms.tok", 550 / 2e6),
    ("head_ms.tok", 50 / 2e6)])
def test_scope_readers_read_ms_a_step(name, ms):
    assert _read(name, _Run(OPS)) == pytest.approx(ms)


@pytest.mark.parametrize("name", [
    "unscoped_share.gap", "unscoped_share.tok", "attn_pack_ms.gap",
    "attn_pack_ms.tok", "dense_ms.gap", "dense_ms.tok", "head_ms.tok"])
def test_new_readers_read_nothing_without_a_trace_or_a_scope(name):
    assert _read(name, _Run(None)) is None
    assert _read(name, _Run([])) is None
    if name.startswith("unscoped"):
        return
    # a parent's trace: the same ops, none under the new scopes
    parent = [[t, s, d, ""] for t, s, d, _ in OPS]
    assert _read(name, _Run(parent)) is None


# ---------------------------------------------------------------------------
# the engine's wait for work
# ---------------------------------------------------------------------------
def _by(events, name):
    return [e for e in events if e["name"] == name]


def test_an_idle_engine_writes_one_span_a_stretch():
    """``replica.idle`` covers the turns that found nothing to do, one
    event a stretch however many 2 ms polls it holds; the turn that finds
    a request closes it before its ``replica.tick``, and the loop's end
    closes the last."""
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256))
    model.eval()
    cluster = ServingCluster(
        lambda: LlamaServingEngine(model, max_batch=4, page_size=8,
                                   num_pages=64, max_pages_per_seq=16,
                                   chunk_budget=16),
        num_replicas=1, ttl=60.0).start()
    try:
        deadline = time.time() + 120
        while not cluster.ready():
            assert time.time() < deadline
            time.sleep(0.02)
        time.sleep(0.1)
        otrace.clear()
        time.sleep(0.3)
        req = cluster.submit(list(np.arange(1, 20)), max_new_tokens=4)
        assert req.wait(120) and req.status == "completed"
        time.sleep(0.3)
    finally:
        cluster.stop()
    events = otrace.get_events()
    idle, ticks = _by(events, "replica.idle"), _by(events, "replica.tick")
    assert ticks and len(idle) == 2
    end = [e["ts"] + e["dur"] for e in idle]
    assert end[0] <= min(t["ts"] for t in ticks)
    assert idle[1]["ts"] >= max(t["ts"] + t["dur"] for t in ticks)
    # the two stretches hold the two 0.3 s waits (µs)
    assert sum(e["dur"] for e in idle) >= 0.55e6
