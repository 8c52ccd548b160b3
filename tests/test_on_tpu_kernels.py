"""Hardware (non-interpret) Pallas kernel tests — `pytest -m tpu`.

The CPU suite runs every kernel in interpret mode; real-TPU tiling bugs
(e.g. the round-1 softmax lane bug fixed in f3e44b8) only surface when
Mosaic compiles the kernel.  These tests re-run the core kernel parity
checks non-interpret; they self-skip unless a TPU is attached (on a
machine with a chip, e.g. through the chip tool):

    APEX_TPU_TEST_ON_TPU=1 python -m pytest tests/test_on_tpu_kernels.py \
        -m tpu -q -p no:cacheprovider

The env var tells tests/conftest.py to keep the real chip instead of
forcing the CPU mesh.  Whether there is a chip is decided inside a
fixture, when the first test of this file starts — never while the
module is imported (every xdist worker imports every test file).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def real_tpu():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs a real TPU chip")


pytestmark = [pytest.mark.tpu, pytest.mark.usefixtures("real_tpu")]


def test_flash_attention_parity_on_chip():
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, 256, 4, 64), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(2, 256, 4, 64), jnp.float32) * 0.5
    v = jnp.asarray(rs.randn(2, 256, 4, 64), jnp.float32) * 0.5
    got = flash_attention(q, k, v, causal=True)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=5e-3, rtol=5e-3)


def test_flash_dropout_statistics_on_chip():
    from apex_tpu.ops.flash_attention import flash_attention

    rs = np.random.RandomState(1)
    b, s, n, d = 1, 256, 2, 128
    q = jnp.asarray(rs.randn(b, s, n, d), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(b, s, n, d), jnp.float32) * 0.5
    v = jnp.asarray(np.tile(np.eye(s)[None, :, None, :d], (b, 1, n, 1)),
                    jnp.float32)
    out = flash_attention(q, k, v, dropout_p=0.4,
                          dropout_rng=jax.random.PRNGKey(3))
    dense = flash_attention(q, k, v)
    ratio = np.asarray(out, np.float64) / np.maximum(
        np.asarray(dense, np.float64), 1e-30)
    zero_frac = 1.0 - (ratio > 0.5).mean()
    assert abs(zero_frac - 0.4) < 0.02


def test_layer_norm_kernel_on_chip():
    from apex_tpu.ops.layer_norm import fused_layer_norm

    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(64, 1024), jnp.float32)
    w = jnp.asarray(1 + 0.1 * rs.randn(1024), jnp.float32)
    b = jnp.asarray(0.1 * rs.randn(1024), jnp.float32)
    got = fused_layer_norm(x, w, b)
    mu = np.asarray(x).mean(-1, keepdims=True)
    var = np.asarray(x).var(-1, keepdims=True)
    want = (np.asarray(x) - mu) / np.sqrt(var + 1e-5)
    want = want * np.asarray(w) + np.asarray(b)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_softmax_kernels_on_chip():
    from apex_tpu.ops.softmax import (
        scaled_softmax, scaled_upper_triang_masked_softmax)

    rs = np.random.RandomState(3)
    s = jnp.asarray(rs.randn(2, 4, 256, 256), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(scaled_softmax(s, 0.5)),
        np.asarray(jax.nn.softmax(np.asarray(s) * 0.5, axis=-1)),
        atol=2e-5, rtol=2e-5)
    got = np.asarray(scaled_upper_triang_masked_softmax(s, 0.5))
    mask = np.triu(np.ones((256, 256), bool), 1)
    ref = np.where(mask[None, None], -1e30, np.asarray(s) * 0.5)
    ref = np.asarray(jax.nn.softmax(ref, axis=-1))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)


# (test_flat_adam_kernel_on_chip was deleted in round 5 along with the
# Pallas flat Adam kernel it Mosaic-validated: the round-5 win-or-delete
# sweep measured it 1.82x the XLA fused update at its best block size.
# The XLA flat update that replaced it has no Mosaic surface; its
# numerics are covered by tests/test_optimizers.py.)


def test_xentropy_kernel_on_chip():
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    rs = np.random.RandomState(5)
    logits = jnp.asarray(rs.randn(64, 512), jnp.float32)
    labels = jnp.asarray(rs.randint(0, 512, (64,)), jnp.int32)
    got = softmax_cross_entropy_loss(logits, labels)
    lse = np.log(np.exp(np.asarray(logits)).sum(-1))
    want = lse - np.asarray(logits)[np.arange(64), np.asarray(labels)]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)


def test_rope_kernel_on_chip():
    from apex_tpu.ops.rope import fused_apply_rotary_pos_emb

    rs = np.random.RandomState(6)
    s, d = 128, 64
    t = jnp.asarray(rs.randn(s, 2, 4, d), jnp.float32)  # [s,b,n,d]
    inv = 1.0 / (10000 ** (np.arange(0, d, 2) / d))
    pos = np.arange(s)[:, None] * inv[None, :]
    freqs = jnp.asarray(
        np.concatenate([pos, pos], -1)[:, None, None, :], jnp.float32)
    got = np.asarray(fused_apply_rotary_pos_emb(t, freqs))
    cos = np.cos(np.concatenate([pos, pos], -1))[:, None, None, :]
    sin = np.sin(np.concatenate([pos, pos], -1))[:, None, None, :]
    x = np.asarray(t)
    rot = np.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    want = x * cos + rot * sin
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_swiglu_kernel_on_chip():
    from apex_tpu.ops.swiglu import fused_bias_swiglu

    rs = np.random.RandomState(7)
    y = jnp.asarray(rs.randn(64, 2 * 256), jnp.float32)
    b = jnp.asarray(rs.randn(2 * 256), jnp.float32)
    got = np.asarray(fused_bias_swiglu(y, b))
    yb = np.asarray(y) + np.asarray(b)
    gate, up = yb[:, :256], yb[:, 256:]
    silu = gate / (1.0 + np.exp(-gate))
    np.testing.assert_allclose(got, silu * up, atol=2e-5, rtol=2e-5)


def test_packed_segment_attention_on_chip():
    """Round-3 varlen kernel: packed rows vs per-sequence oracle with the
    block-sparse skip active on real hardware."""
    from apex_tpu.ops.flash_attention import (
        flash_attention_packed, mha_reference)

    rs = np.random.RandomState(3)
    lengths = [100, 156, 120]
    total = sum(lengths) + 8          # pad tail
    q = jnp.asarray(rs.randn(total, 4, 64), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(total, 4, 64), jnp.float32) * 0.5
    v = jnp.asarray(rs.randn(total, 4, 64), jnp.float32) * 0.5
    cu = jnp.asarray(np.cumsum([0] + lengths), jnp.int32)
    out = jax.jit(lambda q, k, v: flash_attention_packed(
        q, k, v, cu, causal=True))(q, k, v)
    start = 0
    for L in lengths:
        want = mha_reference(
            q[None, start:start + L], k[None, start:start + L],
            v[None, start:start + L], causal=True)[0]
        np.testing.assert_allclose(
            np.asarray(out[start:start + L]), np.asarray(want),
            atol=5e-3, rtol=5e-3)
        start += L
    # pad queries produce exact zeros (l==0 sentinel)
    np.testing.assert_array_equal(
        np.asarray(out[sum(lengths):]), 0.0)


def test_flash_retuned_blocks_on_chip():
    """s1024 path uses the 1024x1024 tiles (round-3 retune) — verify the
    numerics at the exact block-crossover shapes, fwd and bwd."""
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    rs = np.random.RandomState(4)
    for s in (1024, 1536):           # >=1024 triggers the big tiles
        q = jnp.asarray(rs.randn(1, s, 2, 64), jnp.float32) * 0.5
        k = jnp.asarray(rs.randn(1, s, 2, 64), jnp.float32) * 0.5
        v = jnp.asarray(rs.randn(1, s, 2, 64), jnp.float32) * 0.5
        f = jax.jit(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        r = jax.jit(jax.grad(lambda q, k, v: mha_reference(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        for a, b in zip(f(q, k, v), r(q, k, v)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-2, rtol=1e-2)


def test_lm_head_ce_on_chip():
    """Chunked fused head+CE vs the two-stage composition on hardware."""
    from apex_tpu.ops.lm_head_ce import lm_head_cross_entropy
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    rs = np.random.RandomState(5)
    n, h, v = 512, 128, 1024
    hidden = jnp.asarray(rs.randn(n, h) * 0.5, jnp.bfloat16)
    head = jnp.asarray(rs.randn(v, h) * 0.1, jnp.bfloat16)
    labels = jnp.asarray(rs.randint(0, v, (n,)), jnp.int32)

    def fused(hd, he):
        return lm_head_cross_entropy(hd, he, labels, chunk=128).mean()

    def ref(hd, he):
        logits = jnp.einsum("nh,vh->nv", hd, he,
                            preferred_element_type=jnp.float32)
        return softmax_cross_entropy_loss(logits, labels, 0.0, None).mean()

    lf, gf = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(hidden, head)
    lr, gr = jax.jit(jax.value_and_grad(ref, argnums=(0, 1)))(hidden, head)
    np.testing.assert_allclose(float(lf), float(lr), rtol=2e-2)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-2, rtol=2e-2)


def test_fused_flash_backward_on_chip(flash_bwd):
    """The fused single-pass backward vs the split kernels and the
    XLA reference, compiled by Mosaic (non-interpret) at the BERT-class
    short-key shape."""
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(2, 512, 4, 64), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(2, 512, 4, 64), jnp.float32) * 0.5
    v = jnp.asarray(rs.randn(2, 512, 4, 64), jnp.float32) * 0.5
    kpm = jnp.asarray(np.arange(512)[None, :] >= np.array(
        [384, 512])[:, None])

    def grads(causal):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=causal, key_padding_mask=kpm)),
            argnums=(0, 1, 2))(q, k, v)

    for causal in (True, False):
        flash_bwd("fused")
        g_fused = grads(causal)
        flash_bwd("split")
        g_split = grads(causal)
        g_ref = jax.grad(lambda *a: jnp.sum(mha_reference(
            *a, causal=causal, key_padding_mask=kpm)),
            argnums=(0, 1, 2))(q, k, v)
        for gf, gs, gr, nm in zip(g_fused, g_split, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-3, rtol=5e-3,
                err_msg=f"fused d{nm} causal={causal}")
            np.testing.assert_allclose(
                np.asarray(gs), np.asarray(gr), atol=5e-3, rtol=5e-3,
                err_msg=f"split d{nm} causal={causal}")


def test_ln_backward_on_chip():
    """The LayerNorm backward kernel under Mosaic at a multi-block
    shape."""
    from apex_tpu.ops.layer_norm import fused_layer_norm, layer_norm_ref

    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(4096, 768), jnp.bfloat16)
    w = jnp.asarray(1.0 + 0.1 * rs.randn(768), jnp.float32)
    b = jnp.asarray(0.1 * rs.randn(768), jnp.float32)

    def f(x_, w_, b_):
        return jnp.sum(fused_layer_norm(x_, w_, b_).astype(jnp.float32))

    g_ref = jax.grad(
        lambda x_, w_, b_: jnp.sum(
            layer_norm_ref(x_, w_, b_).astype(jnp.float32)),
        argnums=(0, 1, 2))(x, w, b)
    # per-gradient tolerances: dx elements are ~0.1 (a blanket atol=0.5
    # would pass an all-zero dx); dw/db are ~row-count sums where rtol
    # dominates and bf16 accumulation needs the absolute slack
    tols = {"dx": dict(atol=1e-2, rtol=2e-2),
            "dw": dict(atol=0.5, rtol=2e-2),
            "db": dict(atol=0.5, rtol=2e-2)}
    g = jax.grad(f, argnums=(0, 1, 2))(x, w, b)
    for a, r, nm in zip(g, g_ref, ("dx", "dw", "db")):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(r, np.float32),
            err_msg=nm, **tols[nm])


def test_grouped_kv_flash_on_chip(flash_bwd):
    """GQA-aware flash under Mosaic: the grouped index maps (fwd + dq),
    the 4-D dkv accumulation grid, AND the fused kernel's cross-row
    group accumulation only ever ran in interpret mode until a chip is
    attached — tiling/layout bugs surface here."""
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    rs = np.random.RandomState(9)
    q = jnp.asarray(rs.randn(2, 256, 8, 64), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(2, 256, 2, 64), jnp.float32) * 0.5
    v = jnp.asarray(rs.randn(2, 256, 2, 64), jnp.float32) * 0.5
    got = flash_attention(q, k, v, causal=True)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=5e-3, rtol=5e-3)

    g2 = jax.grad(lambda *a: jnp.sum(mha_reference(*a, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for mode in ("split", "fused"):
        flash_bwd(mode)
        g1 = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3,
                err_msg=f"grouped {mode} d{name} on chip")


def test_ring_attention_on_chip():
    """Ring attention's Pallas chunk kernels under Mosaic: single-chip
    mesh (ring of 1 falls back to plain flash; with >1 local devices the
    real ring path runs)."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.ops.flash_attention import mha_reference
    from apex_tpu.parallel.mesh import create_mesh
    from apex_tpu.parallel.ring_attention import ring_attention

    ndev = len(jax.devices())
    sp = min(ndev, 4)
    mesh = create_mesh(sp=sp)
    rs = np.random.RandomState(6)
    q = jnp.asarray(rs.randn(1, 512, 2, 64), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(1, 512, 2, 64), jnp.float32) * 0.5
    v = jnp.asarray(rs.randn(1, 512, 2, 64), jnp.float32) * 0.5

    import functools
    f = jax.jit(jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))
    got = f(q, k, v)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=5e-3, rtol=5e-3)


def test_ssd_scan_kernels_on_chip():
    """The state-space scan's kernels as Mosaic compiles them, bfloat16,
    two groups of eight heads over four chunks: ``y`` and the six
    gradients against the einsum form, and each no further than it from
    the same scan in float32 at full precision."""
    from apex_tpu.ops.ssd_scan import ssd_scan

    ks = jax.random.split(jax.random.key(0), 7)
    bt, s, heads, p, g, n = 1, 512, 16, 64, 2, 128
    args = (jax.random.normal(ks[0], (bt, s, heads, p), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(ks[1], (bt, s, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.7)),
            jax.random.normal(ks[3], (bt, s, g, n), jnp.bfloat16),
            jax.random.normal(ks[4], (bt, s, g, n), jnp.bfloat16),
            jax.random.normal(ks[5], (heads,)))
    w = jax.random.normal(ks[6], (bt, s, heads, p))

    def y_and_grads(backend, args):
        def loss(*a):
            y = ssd_scan(*a, backend=backend).astype(jnp.float32)
            return jnp.vdot(y, w), y
        grads, y = jax.jit(jax.grad(loss, argnums=range(6),
                                    has_aux=True))(*args)
        return (y,) + grads

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    got, form = y_and_grads("kernel", args), y_and_grads("reference", args)
    with jax.default_matmul_precision("highest"):
        exact = y_and_grads("reference", tuple(
            t.astype(jnp.float32) for t in args))
    for name, k, e, x in zip(("y", "x", "dt", "A", "B", "C", "D"),
                             got, form, exact):
        assert rel(k, e) < 1e-2, name
        assert rel(k, x) < 1.5 * rel(e, x) + 1e-4, name
