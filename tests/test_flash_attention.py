"""Flash attention Pallas kernel vs naive XLA composition.

Reference bar: `python/paddle/nn/functional/flash_attention.py:147` —
numerics must match the naive composition (interpret mode on CPU; the
real-chip speed check lives in bench.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import flash_attention as fa


def make_qkv(b=1, s=256, h=2, d=32, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: r.randn(b, s, h, d).astype("float32") * 0.3
    return mk(), mk(), mk()


def naive(q, k, v, causal=False):
    qh = np.transpose(q, (0, 2, 1, 3))
    kh = np.transpose(k, (0, 2, 1, 3))
    vh = np.transpose(v, (0, 2, 1, 3))
    s = qh @ np.swapaxes(kh, -1, -2) / np.sqrt(q.shape[-1])
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((sq, sk), bool))
        s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.transpose(p @ vh, (0, 2, 1, 3))


def test_supported_predicate():
    q, k, v = make_qkv()
    assert fa.supported(paddle.to_tensor(q), paddle.to_tensor(k),
                        paddle.to_tensor(v), None, False)
    small = paddle.to_tensor(q[:, :64])
    assert not fa.supported(small, small, small, None, False)
    assert not fa.supported(paddle.to_tensor(q), paddle.to_tensor(k),
                            paddle.to_tensor(v), paddle.to_tensor(q), False)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal):
    q, k, v = make_qkv()
    out = fa.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                             paddle.to_tensor(v), causal=causal)
    ref = naive(q, k, v, causal)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_naive(causal):
    q, k, v = make_qkv(s=256, d=32)
    g = np.random.RandomState(9).randn(*q.shape).astype("float32")

    ts = [paddle.to_tensor(a, stop_gradient=False) for a in (q, k, v)]
    out = fa.flash_attention(*ts, causal=causal)
    out.backward(paddle.to_tensor(g))

    # reference grads via the naive paddle composition
    ts2 = [paddle.to_tensor(a, stop_gradient=False) for a in (q, k, v)]
    with F.attention.sdp_kernel(enable_flash=False) if hasattr(F, "attention") \
            else _null():
        ref_out = F.scaled_dot_product_attention(*ts2, is_causal=causal)
    ref_out.backward(paddle.to_tensor(g))

    for a, b in zip(ts, ts2):
        np.testing.assert_allclose(a.grad.numpy(), b.grad.numpy(),
                                   rtol=2e-3, atol=2e-3)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_causal_cross_seqlen_matches_naive():
    """sq != sk causal: bottom-right alignment must match the fallback."""
    r = np.random.RandomState(3)
    q = r.randn(1, 128, 2, 32).astype("float32") * 0.3
    k = r.randn(1, 256, 2, 32).astype("float32") * 0.3
    v = r.randn(1, 256, 2, 32).astype("float32") * 0.3
    t = [paddle.to_tensor(a) for a in (q, k, v)]
    paddle.set_flags({"use_pallas_kernels": True})
    a = F.scaled_dot_product_attention(*t, is_causal=True)
    paddle.set_flags({"use_pallas_kernels": False})
    b = F.scaled_dot_product_attention(*t, is_causal=True)
    paddle.set_flags({"use_pallas_kernels": True})
    np.testing.assert_allclose(a.numpy(), b.numpy(), rtol=2e-4, atol=2e-4)


def test_unaligned_seqlen_raises():
    r = np.random.RandomState(4)
    q = paddle.to_tensor(r.randn(1, 200, 2, 32).astype("float32"))
    with pytest.raises(ValueError, match="preconditions"):
        fa.flash_attention(q, q, q)


def test_sdpa_dispatches_to_pallas_and_matches():
    q, k, v = make_qkv(s=128)
    t = [paddle.to_tensor(a) for a in (q, k, v)]
    paddle.set_flags({"use_pallas_kernels": True})
    out_pallas = F.scaled_dot_product_attention(*t)
    paddle.set_flags({"use_pallas_kernels": False})
    out_naive = F.scaled_dot_product_attention(*t)
    paddle.set_flags({"use_pallas_kernels": True})
    np.testing.assert_allclose(out_pallas.numpy(), out_naive.numpy(),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# bf16 inputs: the kernels feed the MXU 16-bit operands (P and dS cast to
# q's dtype) and accumulate in f32; held to the f32 composition of the same
# bf16 values, relative to its largest magnitude (measured 2e-3 - 4.5e-3)
# ---------------------------------------------------------------------------
BF16_RTOL = 1.5e-2


def _reference(q, k, v, causal, scale):
    """f32 attention, GQA by repetition, causal bottom-right aligned."""
    sq, h, sk, hk = q.shape[1], q.shape[2], k.shape[1], k.shape[2]
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    if causal:
        s = jnp.where(np.arange(sk)[None, :] <= np.arange(sq)[:, None]
                      + sk - sq, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _bf16_case(sq, sk, h, hk, causal, d=64, seed=0, rows_with_grad=None):
    """(kernel's out, dq, dk, dv; the f32 reference's) on bf16 inputs."""
    r = np.random.RandomState(seed)
    bf = lambda *s: jnp.asarray(r.randn(*s), jnp.bfloat16)  # noqa: E731
    q, k, v, g = bf(1, sq, h, d), bf(1, sk, hk, d), bf(1, sk, hk, d), \
        bf(1, sq, h, d)
    if rows_with_grad is not None:
        g = g.at[:, rows_with_grad:].set(0)
    scale = d ** -0.5
    out, vjp = jax.vjp(fa._make_flash(scale, causal, h // hk), q, k, v)
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    ref, ref_vjp = jax.vjp(lambda *a: _reference(*a, causal, scale),
                           f32(q), f32(k), f32(v))
    return (out,) + vjp(g), (ref,) + ref_vjp(f32(g))


@pytest.mark.parametrize("sq,sk,h,hk,causal", [
    (s, s, h, hk, c) for s in (128, 384, 1024)
    for h, hk in ((2, 2), (4, 2), (4, 1)) for c in (True, False)] + [
    (128, 384, 4, 2, True), (256, 1024, 4, 1, True),
    (512, 640, 4, 2, True)],
    ids=lambda a: str(a))
def test_bf16_fwd_bwd_match_f32_reference(sq, sk, h, hk, causal):
    """S = 128 takes 128-wide blocks everywhere, 384 too (256 and 512 do
    not divide it), 1024 the widest; ``sq < sk`` meets the diagonal at a
    non-zero offset, with query blocks narrower than the key blocks (256
    over 512: unmasked steps of 256 after the wide blocks) and wider
    (512 over 128: rows that see nothing of a masked step)."""
    got, want = _bf16_case(sq, sk, h, hk, causal)
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16, name
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - w)))
        assert err <= BF16_RTOL * float(jnp.max(jnp.abs(w))), (name, err)


@pytest.mark.parametrize("window", [None, 128], ids=["whole", "windows"])
def test_dkdv_zero_for_a_key_block_no_query_sees(window, monkeypatch):
    """Causal, S = 384: query rows 256-383 carry no gradient, and no row
    before them sees keys 256-383, so that key block's dK and dV are
    exactly zero; the blocks before it are not. ``windows`` walks each
    query head in windows of 128 rows (what a head too long for VMEM
    takes), so that dead windows are skipped too."""
    if window:
        monkeypatch.setattr(fa, "_dkv_window", lambda *a: window)
    got, want = _bf16_case(384, 384, 4, 2, True, rows_with_grad=256)
    dk, dv = got[2], got[3]
    assert not jnp.any(dk[:, 256:]) and not jnp.any(dv[:, 256:])
    assert jnp.all(jnp.any(dk[:, :256] != 0, axis=-1))
    for a, w in zip(got, want):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - w)))
        assert err <= BF16_RTOL * float(jnp.max(jnp.abs(w)))


def test_mesh_attention_matches_one_device_fwd_and_bwd():
    """``shard_llama``'s attention path: the kernel per device under
    ``shard_map`` (dp2 x mp2 over the virtual devices) gives the
    one-device answer and gradients — GQA heads split over ``mp``."""
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.models.llama import _mesh_attention

    mesh = ProcessMesh(np.arange(4).reshape(2, 2), dim_names=["dp", "mp"])
    r = np.random.RandomState(3)
    q = r.randn(2, 128, 4, 32).astype("float32") * 0.3
    k = r.randn(2, 128, 2, 32).astype("float32") * 0.3
    v = r.randn(2, 128, 2, 32).astype("float32") * 0.3

    def run(attend):
        ts = [paddle.to_tensor(a, stop_gradient=False) for a in (q, k, v)]
        out = attend(*ts)
        out.sum().backward()
        return [out.numpy()] + [t.grad.numpy() for t in ts]

    got = run(lambda a, b, c: _mesh_attention(a, b, c, mesh, ("dp",), "mp"))
    want = run(lambda a, b, c: F.scaled_dot_product_attention(
        a, b, c, is_causal=True))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
