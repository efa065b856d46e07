"""Ask the chip's compiler before the chip.

Every kernel of the two main paths (Llama serving, Llama training) is
compiled here for a TPU v5e that is described and not attached, at
published widths. Interpret-mode tests cannot see what Mosaic refuses
(a slice not aligned to the tiling, more VMEM than a kernel may use);
these can, at no chip time. Nothing runs: a compile that passes is not
a chip run.

The topology is described inside a module-scoped fixture of THIS file
(never at import: only one process may hold the TPU library, and every
xdist worker imports every test file). The kernels read
``jax.default_backend()``, which is ``cpu`` under test, so the tests
steer them off interpret mode with ``monkeypatch``.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import fused_linear_cross_entropy as flce
from paddle_tpu.ops import grouped_gemm as gg
from paddle_tpu.ops import kda
from paddle_tpu.ops import ragged_mla_attention as mla
from paddle_tpu.ops import ragged_paged_attention as rpa
from paddle_tpu.quant import kernels as qk

BF16 = jnp.bfloat16
F32 = jnp.float32
I32 = jnp.int32
I8 = jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def chip_compile(one_chip, no_persistent_cache, monkeypatch):
    """``compile(fn, *specs) -> compiled``: ``fn`` jitted and compiled
    for one described v5e with every kernel module off interpret mode.
    ``specs`` are ``(shape, dtype)`` pairs."""
    for mod in (rpa, fa, flce, gg, qk, mla, kda):
        monkeypatch.setattr(mod, "_interpret", lambda: False)

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile()

    return compile_


def _custom_calls(text):
    """Instruction names of the Mosaic custom calls in compiled HLO."""
    return re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                      text)


def _assert_kernel(compiled, *names):
    """The kernels are in, and the compiled HLO calls each by the name
    its ``pallas_call`` passes: a profiler trace names a device op by
    its instruction, so this is what a reader's pattern will see."""
    calls = _custom_calls(compiled.as_text())
    assert calls
    found = {m.group(0) for c in calls
             for m in [re.search(r"paddle_tpu\.[a-z0-9]+(?:_[a-z0-9]+)*", c)]
             if m}
    assert all(re.search(r"paddle_tpu\.", c) for c in calls), calls
    assert found == {"paddle_tpu." + n for n in names}, (found, calls)


# ---------------------------------------------------------------------------
# serving: the two ragged paged-attention programs (one a pool dtype) at
# Llama-3-8B head geometry and the engine's defaults for max_batch=16
# ---------------------------------------------------------------------------
H, HK, D = 32, 8, 128
R, T, QB = 18, 64, 32
MAX_CTX = 1152


def _ragged_specs(q8, pool, rows, tokens, width, group=4):
    """``(shape, dtype)`` of a ragged program's operands: packed q
    (``group`` query heads a kv head), new K/V, the pools (int8 with
    f32 sidecars where ``q8``), tables, the six per-row arrays, the
    sin/cos tables."""
    h, d = group * pool[1], pool[3]
    pools = [(pool, I8 if q8 else BF16)] * 2 \
        + ([(pool[:3] + (1,), F32)] * 2 if q8 else [])
    return [((tokens, h, d), BF16)] + [((tokens, pool[1], d), BF16)] * 2 \
        + pools + [((rows, width), I32)] + [((rows,), I32)] * 6 \
        + [((tokens, d), F32)] * 2


RAGGED_KERNELS = {
    "_fused_rope_impl": "ragged_attn_fused_rope",
    "_fused_rope_impl_q8": "ragged_attn_fused_rope_q8"}


@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("program", list(RAGGED_KERNELS))
def test_ragged_programs_compile_bf16(chip_compile, program, page_size):
    fn = functools.partial(getattr(rpa, program), scale=D ** -0.5,
                           dump_page=0, qblock=QB)
    pool = (4 * MAX_CTX // page_size, HK, page_size, D)
    specs = _ragged_specs(program.endswith("q8"), pool, R, T,
                          MAX_CTX // page_size)
    _assert_kernel(chip_compile(fn, *specs), RAGGED_KERNELS[program])


# both programs at Mistral-7B's head shape and the benchmark's pool:
# both of chat-open's step programs (36 rows x 128 tokens in 32-token
# blocks; 32 decode rows) behind tables of the two serving cells' widths
# and, for the float program, of a long-context cell's. The float walk
# is bounded by kv_lens, so the width only sizes its table in SMEM; the
# int8 program's grid has a step a table slot (ROADMAP S8c starts here).
# The float program's mixed shape holds both of a row's sizes (ISSUE 36:
# the small tile of `small_tile(group)` softmax rows for a row whose
# query tokens fit it, the whole query block otherwise): 8 of 128 rows
# at the cells' 4 query heads a kv head, where a tile is two tokens,
# and 8 of 256 at 8, where it is one token's rows exactly.
MISTRAL_POOL = (4097, 8, 16, 128)
SHAPES = [(36, 128, 32, 4), (32, 32, 1, 4), (36, 128, 32, 8)]
SHAPE_IDS = ["mixed", "decode", "mixed-group8"]


@pytest.mark.parametrize("program,width", [
    ("_fused_rope_impl", 66), ("_fused_rope_impl", 161),
    ("_fused_rope_impl", 521), ("_fused_rope_impl_q8", 66),
    ("_fused_rope_impl_q8", 161)],
    ids=["float-66", "float-161", "float-521", "int8-66", "int8-161"])
@pytest.mark.parametrize("rows,tokens,qblock,group", SHAPES, ids=SHAPE_IDS)
def test_walk_compiles_at_mistral_widths(chip_compile, rows, tokens,
                                         qblock, group, program, width):
    q8 = program.endswith("q8")
    fn = functools.partial(getattr(rpa, program), dump_page=4096,
                           scale=D ** -0.5, qblock=qblock)
    compiled = chip_compile(
        fn, *_ragged_specs(q8, MISTRAL_POOL, rows, tokens, width, group))
    _assert_kernel(compiled, RAGGED_KERNELS[program])
    # the one custom call a layer whose result holds both pools: what
    # the benchmark's attention roofline finds the kernel by
    dt = "s8" if q8 else "bf16"
    assert re.search(dt + r"\[4097,8,16,128\][^\n]*" + dt
                     + r"\[4097,8,16,128\]"
                     r"[^\n]*custom_call_target=\"tpu_custom_call\"",
                     compiled.as_text())


# the float program as the hybrid family's three attention kinds call
# it: 40 padded query heads over 10 pairs of key heads (128 lanes), page
# 16, no rope tables (the model has no positional encoding), behind the
# window layers' ring tables (window 512), the shared pool's tables, and
# read-only for the layers that own no pool
@pytest.mark.parametrize("kind", ["window", "full", "cross"])
@pytest.mark.parametrize("rows,tokens,qblock,group", [
    (102, 192, 32, 4), (96, 96, 1, 4), (102, 192, 32, 8)], ids=SHAPE_IDS)
def test_walk_compiles_for_window_and_cross_layers(chip_compile, rows,
                                                   tokens, qblock, group,
                                                   kind):
    pool = ((96 * 36 + 1) if kind == "window" else 30721, 10, 16, 128)
    fn = functools.partial(
        rpa._fused_rope_impl, dump_page=pool[0] - 1, scale=0.125,
        qblock=qblock, window=512 if kind == "window" else None,
        read_only=kind == "cross", rope_sin=None, rope_cos=None)
    compiled = chip_compile(fn, *_ragged_specs(False, pool, rows, tokens,
                                               320, group)[:-2])
    _assert_kernel(compiled, "ragged_attn_fused_rope")


# the float program as the window / full expert family calls it: 32 query
# heads over 4 kv heads of 128 (a group of 8: a decode row's small tile is
# one token's rows), page 16, behind the window layers' ring tables
# (window 1,024, 65 slots of 97 pages) and the full layers' pool of the
# code cell (49,153 pages, tables 1,089 wide), both of its step shapes
@pytest.mark.parametrize("kind", ["window", "full"])
@pytest.mark.parametrize("rows,tokens,qblock", [(80, 512, 32), (64, 64, 1)],
                         ids=["mixed", "decode"])
def test_walk_compiles_at_the_code_cell(chip_compile, rows, tokens, qblock,
                                        kind):
    window = kind == "window"
    pool = (65 * 97 if window else 49153, 4, 16, 128)
    fn = functools.partial(
        rpa._fused_rope_impl, dump_page=pool[0] - 1, scale=D ** -0.5,
        qblock=qblock, window=1024 if window else None)
    compiled = chip_compile(fn, *_ragged_specs(False, pool, rows, tokens,
                                               1089, 8))
    _assert_kernel(compiled, "ragged_attn_fused_rope")


# the float program as the MTP-drafting family calls it: 64 query heads
# over 8 kv heads of 128 (a group of 8), page 16, behind the window
# layers' ring tables (window 128, 65 slots of 41 pages) and, with no
# rope tables (the NoPE full layers and the MTP module's), the whole-
# context pool of the reason-mtp cell (16,385 pages, tables 769 wide),
# both of its step shapes, with the small tile of its engine's verify
# row (``spec_k = 1``: two tokens, 16 softmax rows)
@pytest.mark.parametrize("kind", ["window", "nope"])
@pytest.mark.parametrize("rows,tokens,qblock", [(80, 512, 32), (64, 64, 1)],
                         ids=["mixed", "decode"])
def test_walk_compiles_at_the_reason_mtp_cell(chip_compile, rows, tokens,
                                              qblock, kind):
    window = kind == "window"
    pool = (65 * 41 if window else 16385, 8, 16, 128)
    fn = functools.partial(
        rpa._fused_rope_impl, dump_page=pool[0] - 1, scale=D ** -0.5,
        qblock=qblock, window=128 if window else None, tile_tokens=2)
    specs = _ragged_specs(False, pool, rows, tokens, 769, 8)
    if not window:
        fn = functools.partial(fn, rope_sin=None, rope_cos=None)
        specs = specs[:-2]
    compiled = chip_compile(fn, *specs)
    _assert_kernel(compiled, "ragged_attn_fused_rope")


# ---------------------------------------------------------------------------
# training: flash attention fwd+bwd, fused CE, and the quantized /
# grouped matmuls
# ---------------------------------------------------------------------------
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkdv")


def _flash_grad(group):
    flash = fa._make_flash(D ** -0.5, True, group)
    return jax.grad(lambda q, k, v: flash(q, k, v).astype(F32).sum(),
                    argnums=(0, 1, 2))


def _flash_specs(b, s, h, hk, dtype):
    return [((b, s, h, D), dtype)] + [((b, s, hk, D), dtype)] * 2


@pytest.mark.parametrize("b,s,h,hk", [
    (2, 2048, 16, 8),       # the "0.5b" recipe of examples/llama_pretrain
    (1, 4096, 32, 8),       # Llama-3-8B heads at half its context
])
def test_flash_fwd_bwd_compiles_bf16(chip_compile, b, s, h, hk):
    _assert_kernel(chip_compile(_flash_grad(h // hk),
                                *_flash_specs(b, s, h, hk, BF16)),
                   *FLASH_KERNELS)


def test_flash_compiles_at_the_training_cell(chip_compile):
    """pretrain-4k's attention (2 x 4096, 16 query heads over 8 kv heads
    of 128, bf16): the kernels compile, and each returns the bf16
    ``[batch, heads, seq, head_dim]`` result the benchmark's
    ``flash_roofline.train`` finds it by."""
    compiled = chip_compile(_flash_grad(2), *_flash_specs(2, 4096, 16, 8,
                                                          BF16))
    _assert_kernel(compiled, *FLASH_KERNELS)
    text = compiled.as_text()
    for name, heads in (("flash_fwd", 16), ("flash_dq", 16),
                        ("flash_dkdv", 8)):
        line = next(ln for ln in text.splitlines()
                    if "paddle_tpu." + name in ln.split("=")[0]
                    and "tpu_custom_call" in ln)
        assert re.search(r"= \(?bf16\[2,%d,4096,128\]" % heads, line), line


def test_flash_supported_agrees_with_compiler(chip_compile):
    """Whatever ``supported()`` accepts the compiler accepts; the walk
    must really reach Llama-3-8B's own context in bf16. The largest
    lengths it accepts (K and V of a kv head at the budget: 12,288 in
    bf16, 6,144 in f32) also walk the dK/dV kernel's query rows in
    windows, a whole head's not fitting its budget."""
    walk = [(1, 8192, 16, 8, BF16), (1, 8192, 32, 8, BF16),
            (2, 2048, 32, 8, F32), (1, 4096, 32, 8, F32),
            (1, 8192, 32, 8, F32), (1, 16384, 32, 8, BF16),
            (1, 12288, 32, 8, BF16), (1, 12416, 32, 8, BF16),
            (1, 6144, 32, 8, F32)]
    accepted = []
    for b, s, h, hk, dtype in walk:
        specs = _flash_specs(b, s, h, hk, dtype)
        q, k, v = (jax.ShapeDtypeStruct(sh, dt) for sh, dt in specs)
        if fa.supported(q, k, v, None, True):
            accepted.append((b, s, h, hk, dtype))
            _assert_kernel(chip_compile(_flash_grad(h // hk), *specs),
                           *FLASH_KERNELS)
    assert (1, 8192, 32, 8, BF16) in accepted
    assert (1, 8192, 16, 8, BF16) in accepted
    assert (1, 12288, 32, 8, BF16) in accepted
    assert (1, 6144, 32, 8, F32) in accepted
    assert (1, 12416, 32, 8, BF16) not in accepted
    assert fa._dkv_window(12288, D, 2, 128) < 12288
    assert fa._dkv_window(6144, D, 4, 128) < 6144


def test_flash_under_a_mesh_compiles_for_2x2(topo, no_persistent_cache,
                                             monkeypatch):
    """A sharded train step cannot hold a bare Mosaic kernel (GSPMD
    cannot partition one): ``shard_llama`` routes attention through
    ``_mesh_attention``, which must compile for the described 2x2 with
    its kernels in and no collective (attention mixes neither batch nor
    heads)."""
    import types

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.models import llama

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    jmesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "mp"))
    mesh = types.SimpleNamespace(dim_names=["dp", "mp"], shape=[2, 2],
                                 to_jax_mesh=lambda: jmesh)

    def loss(q, k, v):
        out = llama._mesh_attention(Tensor(q), Tensor(k), Tensor(v), mesh,
                                    ("dp",), "mp")
        return out._data.astype(F32).sum()

    sharding = NamedSharding(jmesh, PartitionSpec("dp", None, "mp", None))
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in _flash_specs(2, 2048, 16, 8, BF16)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert all(any("paddle_tpu." + k in c for c in _custom_calls(text))
               for k in FLASH_KERNELS)
    assert "all-gather" not in text and "all-reduce" not in text


# the last: pretrain-4k's head in f32 at the 1,024-row tile it picks,
# which asks for scoped VMEM above the compiler's 16 MB default
@pytest.mark.parametrize("n,d,v,dtype", [
    (4096, 2048, 32000, BF16), (4096, 4096, 128256, BF16),
    (4096, 4096, 128256, F32), (8192, 2048, 92544, F32)])
def test_fused_ce_compiles(chip_compile, n, d, v, dtype):
    _assert_kernel(chip_compile(
        flce._kernel_parts, ((n, d), dtype), ((d, v), dtype), ((n,), I32)),
        "fused_ce")


def test_dequant_matmul_compiles_bf16(chip_compile):
    m, k, n, block = 16, 4096, 14336, 128
    _assert_kernel(chip_compile(
        functools.partial(qk._kernel_impl, block=block),
        ((m, k), BF16), ((k, n), I8), ((k // block, n), F32)),
        "dequant_matmul")


def test_grouped_gemm_compiles_bf16(chip_compile):
    e, c, k, n = 8, 128, 4096, 14336
    _assert_kernel(chip_compile(
        gg._grouped_impl, ((e * c, k), BF16), ((e, k, n), BF16),
        ((e,), I32)), "grouped_gemm")


@pytest.mark.parametrize("m,k,n,block_m", [
    (16128, 2048, 768, 32),     # a 1,024-token dispatch: gate and up
    (16128, 768, 2048, 32),     # ... and down
    (4224, 2048, 768, 16),      # the 48-token decode-only dispatch
])
def test_packed_grouped_gemm_compiles_at_published_widths(chip_compile, m,
                                                          k, n, block_m):
    """256 experts of width 768 over hidden 2048: the packed rows of the
    latent-attention expert family's two step shapes."""
    assert gg.packed_rows(1024 * 8, 256, 32) == 16128
    assert gg.packed_rows(48 * 8, 256, 16) == 4224
    _assert_kernel(chip_compile(
        functools.partial(gg._packed_kernel_impl, block_m=block_m),
        ((m, k), BF16), ((256, k, n), BF16), ((m // block_m,), I32),
        ((1,), I32)), "grouped_gemm_packed")


@pytest.mark.parametrize("tokens,k,n", [
    (512, 2304, 896), (512, 896, 2304), (64, 2304, 896), (64, 896, 2304)],
    ids=["mixed-in", "mixed-down", "decode-in", "decode-down"])
def test_packed_grouped_gemm_compiles_at_the_code_cell(chip_compile, tokens,
                                                       k, n):
    """64 experts of width 896 over hidden 2304, 8 a token: the packed
    rows of the window / full expert family's two step shapes."""
    block_m = gg.packed_block_m(tokens * 8, 64)
    m = gg.packed_rows(tokens * 8, 64, block_m)
    assert (m, block_m) in ((8128, 64), (1472, 16))
    _assert_kernel(chip_compile(
        functools.partial(gg._packed_kernel_impl, block_m=block_m),
        ((m, k), BF16), ((64, k, n), BF16), ((m // block_m,), I32),
        ((1,), I32)), "grouped_gemm_packed")


@pytest.mark.parametrize("tokens,rows,qblock,pages,table", [
    (1024, 80, 32, 24577, 1057), (48, 48, 1, 24577, 1057),
    (512, 72, 64, 8193, 385), (64, 64, 1, 8193, 385)],
    ids=["docqa-mixed", "docqa-decode", "assist-mixed", "assist-decode"])
def test_latent_attention_compiles_at_published_widths(chip_compile,
                                                       tokens, rows,
                                                       qblock, pages,
                                                       table):
    """32 heads over a 640-lane latent row (512 + 64 in use), pages of
    16: the docqa cell's two step shapes (a table 1,057 pages wide) and
    the assist cell's (chunks of 64, a table of 385)."""
    width = mla.latent_row_width(512, 64)
    _assert_kernel(chip_compile(
        functools.partial(mla._kernel_impl, v_width=512,
                          scale=192 ** -0.5, qblock=qblock),
        ((tokens, 32, width), BF16), ((tokens, width), BF16),
        ((pages, 16, width), BF16), ((rows, table), I32),
        *[((rows,), I32)] * 6), "ragged_mla_attn")


@pytest.mark.parametrize("m,k,n,block_m", [
    (6144, 2304, 1024, 128), (6144, 1024, 2304, 128),
    (1024, 2304, 1024, 32)],
    ids=["mixed-gate-up", "mixed-down", "decode-gate-up"])
def test_packed_grouped_gemm_compiles_for_an_expert_share(chip_compile, m,
                                                          k, n, block_m):
    """16 held experts of width 1024 over hidden 2304: the worst-case
    packed rows (every assignment on a held expert) of the assist cell's
    512-token and 64-token steps, in the row tiles the layer picks."""
    assert gg.packed_block_m(512 * 8, 16, sublane=16) == 128
    assert gg.packed_rows(512 * 8, 16, 128) == 6144
    assert gg.packed_block_m(64 * 8, 16, sublane=16) == 32
    assert gg.packed_rows(64 * 8, 16, 32) == 1024
    _assert_kernel(chip_compile(
        functools.partial(gg._packed_kernel_impl, block_m=block_m),
        ((m, k), BF16), ((16, k, n), BF16), ((m // block_m,), I32),
        ((1,), I32)), "grouped_gemm_packed")


@pytest.mark.parametrize("m,k,n,block_m", [
    (5120, 6144, 2048, 128), (5120, 2048, 6144, 128),
    (1024, 6144, 2048, 64), (1024, 2048, 6144, 64)],
    ids=["mixed-gate-up", "mixed-down", "decode-gate-up", "decode-down"])
def test_packed_grouped_gemm_compiles_at_the_reason_mtp_cell(chip_compile, m,
                                                             k, n, block_m):
    """8 held experts of width 2048 over hidden 6144: the worst-case
    packed rows of the reason-mtp cell's 512-token and 64-token steps."""
    assert gg.packed_block_m(512 * 8, 8, sublane=16) == 128
    assert gg.packed_rows(512 * 8, 8, 128) == 5120
    assert gg.packed_block_m(64 * 8, 8, sublane=16) == 64
    assert gg.packed_rows(64 * 8, 8, 64) == 1024
    _assert_kernel(chip_compile(
        functools.partial(gg._packed_kernel_impl, block_m=block_m),
        ((m, k), BF16), ((8, k, n), BF16), ((m // block_m,), I32),
        ((1,), I32)), "grouped_gemm_packed")


@pytest.mark.parametrize("rows", [72, 64], ids=["mixed", "decode"])
def test_kda_step_compiles_at_published_widths(chip_compile, rows):
    """32 heads of 128 x 128 float32 a row against a pool of 65 slots,
    updated in place: no second pool in the compiled program."""
    h, d, slots = 32, 128, 65
    c = chip_compile(
        kda.kda_step, ((rows, h, d), F32), ((rows, h, d), F32),
        ((rows, h, d), F32), ((rows, h, d), F32), ((rows, h), F32),
        ((slots, h, d, d), F32), ((rows,), I32), ((rows,), I32))
    _assert_kernel(c, "kda_step")


def test_kda_chunk_rows_compile_at_published_widths(chip_compile):
    """Eight chunk rows of 64 tokens, one row at a time (XLA: no
    kernel): the triangular solve and the loop lower for the chip."""
    rows, q, h, d = 8, 64, 32, 128
    c = chip_compile(
        functools.partial(kda.kda_rows, long_rows=rows),
        ((rows, q, h, d), F32), ((rows, q, h, d), F32),
        ((rows, q, h, d), F32), ((rows, q, h, d), F32),
        ((rows, q, h), F32), ((rows, h, d, d), F32), ((rows,), I32))
    assert "while" in c.as_text()
