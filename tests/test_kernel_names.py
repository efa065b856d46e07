"""Every ``pallas_call`` of the program passes ``name=``: one prefix,
lower case, no shape in a name, no two sites alike. A profiler trace (and
the benchmark's readers) finds a kernel's events by this name alone, so a
site that drops its name falls out of every roofline silently."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import fused_linear_cross_entropy as flce
from paddle_tpu.ops import grouped_gemm as gg
from paddle_tpu.ops import kda
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import ragged_mla_attention as mla
from paddle_tpu.ops import ragged_paged_attention as rpa
from paddle_tpu.quant import kernels as qk

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
H, HK, D = 4, 2, 128
R, T, QB, PAGE, PAGES, WIDTH = 3, 16, 8, 8, 12, 4


def _ragged(program):
    """(fn, specs) of one of the two ragged programs, as
    tests/test_chip_compile.py builds them, at a toy size."""
    pool, sidecar = (PAGES, HK, PAGE, D), (PAGES, HK, PAGE, 1)
    q8 = program.endswith("q8")
    pools = [(pool, I8 if q8 else BF16)] * 2 \
        + ([(sidecar, F32)] * 2 if q8 else [])
    specs = [((T, H, D), BF16)] + [((T, HK, D), BF16)] * 2 + pools \
        + [((R, WIDTH), I32)] + [((R,), I32)] * 6 + [((T, D), F32)] * 2
    return functools.partial(getattr(rpa, program), scale=D ** -0.5,
                             dump_page=0, qblock=QB), specs


def _flash_grad():
    flash = fa._make_flash(D ** -0.5, True, H // HK)
    fn = jax.grad(lambda q, k, v: flash(q, k, v).astype(F32).sum(),
                  argnums=(0, 1, 2))
    return fn, [((1, 256, H, D), BF16)] + [((1, 256, HK, D), BF16)] * 2


#: site -> (its name, how to trace a program that holds it)
SITES = {
    "ragged_paged_attention.py fused rope": (
        "paddle_tpu.ragged_attn_fused_rope",
        lambda: _ragged("_fused_rope_impl")),
    "ragged_paged_attention.py fused rope q8": (
        "paddle_tpu.ragged_attn_fused_rope_q8",
        lambda: _ragged("_fused_rope_impl_q8")),
    "flash_attention.py forward": ("paddle_tpu.flash_fwd", _flash_grad),
    "flash_attention.py dq": ("paddle_tpu.flash_dq", _flash_grad),
    "flash_attention.py dkdv": ("paddle_tpu.flash_dkdv", _flash_grad),
    "fused_linear_cross_entropy.py": (
        "paddle_tpu.fused_ce", lambda: (
            flce._kernel_parts,
            [((64, 128), F32), ((128, 512), F32), ((64,), I32)])),
    "grouped_gemm.py": (
        "paddle_tpu.grouped_gemm", lambda: (
            gg._grouped_impl,
            [((4 * 8, 128), BF16), ((4, 128, 128), BF16), ((4,), I32)])),
    "grouped_gemm.py q8": (
        "paddle_tpu.grouped_gemm_q8", lambda: (
            functools.partial(gg._q8_impl, block=128),
            [((4 * 8, 128), BF16), ((4, 128, 128), I8), ((4, 1, 128), F32),
             ((4,), I32)])),
    "grouped_gemm.py packed": (
        "paddle_tpu.grouped_gemm_packed", lambda: (
            functools.partial(gg._packed_kernel_impl, block_m=16),
            [((6 * 16, 128), BF16), ((4, 128, 128), BF16), ((6,), I32),
             ((1,), I32)])),
    "ragged_mla_attention.py": (
        "paddle_tpu.ragged_mla_attn", lambda: (
            functools.partial(mla._kernel_impl, v_width=128, scale=0.1,
                              qblock=QB),
            [((T, H, 256), BF16), ((T, 256), BF16),
             ((PAGES, PAGE, 256), BF16), ((R, WIDTH), I32)]
            + [((R,), I32)] * 6)),
    "paged_attention.py": (
        "paddle_tpu.paged_attn_decode", lambda: (
            functools.partial(pa._paged_impl, scale=D ** -0.5),
            [((R, H, D), BF16), ((PAGES, HK, PAGE, D), BF16),
             ((PAGES, HK, PAGE, D), BF16), ((R, WIDTH), I32),
             ((R,), I32)])),
    "kda.py step": (
        "paddle_tpu.kda_step", lambda: (
            kda.kda_step,
            [((R, H, D), F32)] * 4 + [((R, H), F32),
                                      ((R + 1, H, D, D), F32),
                                      ((R,), I32), ((R,), I32)])),
    "quant/kernels.py": (
        "paddle_tpu.dequant_matmul", lambda: (
            functools.partial(qk._kernel_impl, block=128),
            [((16, 256), BF16), ((256, 256), I8), ((2, 256), F32)])),
}


def _kernel_names(jaxpr, out):
    """Names of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.params["name"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _kernel_names(inner, out)
    return out


@pytest.mark.parametrize("site", sorted(SITES))
def test_pallas_call_site_carries_its_name(site):
    name, build = SITES[site]
    fn, specs = build()
    args = [jax.ShapeDtypeStruct(s, d) for s, d in specs]
    names = _kernel_names(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert names, f"{site}: no pallas_call in the traced program"
    assert name in names, f"{site}: found {names}"
    for n in names:
        assert re.fullmatch(r"paddle_tpu\.[a-z0-9]+(_[a-z0-9]+)*", n), n


def test_names_are_distinct_and_cover_every_site():
    import pathlib
    names = [n for n, _ in SITES.values()]
    assert len(set(names)) == len(names) == 13
    root = pathlib.Path(fa.__file__).parent.parent
    calls = named = 0
    for path in root.rglob("*.py"):
        text = path.read_text()
        calls += len(re.findall(r"\bpl\.pallas_call\(", text))
        named += len(re.findall(r"\bname=(?:\"paddle_tpu\.|KERNEL_NAME)",
                                text))
    assert calls == named == 13
