"""The state-space scan's Pallas kernels (ops/ssd_scan.py) in interpret mode
on the CPU: ``backend="kernel"`` against ``backend="reference"`` (the
einsum form, backward by autodiff) and against the recurrence taken
position by position (the benchmark's float32 reference), ``y`` and the
gradients of x, dt, A, B, C and D; and the shapes that must fall back.
What Mosaic makes of the kernels is tests/test_tpu_aot_compile.py's.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.ops.ssd_scan import ssd_scan, ssd_scan_packed    # noqa: E402
from benchmark.reference import nemotron_h as ref               # noqa: E402

NAMES = ("x", "dt", "A", "B", "C", "D")


def _case(seed, dtype, s=256, bt=1, heads=2, p=64, g=1, n=128):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (bt, s, heads, p), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (bt, s, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.7)),
            jax.random.normal(ks[3], (bt, s, g, n), dtype),
            jax.random.normal(ks[4], (bt, s, g, n), dtype),
            jax.random.normal(ks[5], (heads,))), jax.random.normal(
                ks[6], (bt, s, heads, p))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _y_and_grads(scan, args, w):
    def loss(*a):
        y = scan(*a).astype(jnp.float32)
        return jnp.vdot(y, w), y

    grads, y = jax.grad(loss, argnums=range(6), has_aux=True)(*args)
    return (y,) + grads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [
    pytest.param(dict(s=256), id="two_chunks"),
    pytest.param(dict(s=1024), id="eight_chunks"),
    pytest.param(dict(s=256, heads=4, g=2), id="two_groups"),
    pytest.param(dict(s=200), id="padded_to_the_chunk"),
    pytest.param(dict(s=256, bt=2), id="batch_2"),
    pytest.param(dict(s=128, heads=8), id="a_group_of_eight_heads"),
])
def test_kernels_against_the_einsum_form_and_the_recurrence(shape, dtype):
    """``y`` and all six gradients of the kernels, of the einsum form and
    of the recurrence on the same inputs.  In float32 the three agree to
    rounding; in bfloat16 the two chunked forms round the same products'
    operands, and each is as far from the float32 recurrence as the
    other."""
    args, w = _case(7, dtype, **shape)
    got = _y_and_grads(lambda *a: ssd_scan(*a, backend="kernel"), args, w)
    form = _y_and_grads(lambda *a: ssd_scan(*a, backend="reference"),
                        args, w)
    f32 = tuple(t.astype(jnp.float32) for t in args)
    want = _y_and_grads(ref.recurrence, f32, w)
    exact = dtype == jnp.float32
    for name, k, e, r in zip(("y",) + NAMES, got, form, want):
        assert k.dtype == e.dtype and k.shape == e.shape, name
        assert _rel(k, e) < (2e-5 if exact else 1e-2), name
        assert _rel(k, r) < (2e-5 if exact else 1e-2), name
        if not exact:
            assert _rel(k, r) < 1.5 * _rel(e, r) + 1e-4, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_packed_kernels_read_x_b_c_out_of_one_array(dtype):
    """``ssd_scan_packed`` on ``[x | B | C]`` (the mixer's call): the
    kernels take the three parts by column block and the gradient comes
    back as one array; equal to the scan on the parts."""
    (x, dt, a, b, c, d), w = _case(8, dtype, s=200, bt=2, heads=4, g=2)
    bt, s = x.shape[:2]
    xbc = jnp.concatenate([t.reshape(bt, s, -1) for t in (x, b, c)], -1)

    def packed(backend):
        def loss(xbc, dt, a, d):
            y = ssd_scan_packed(xbc, dt, a, d, groups=2, state=128,
                                backend=backend)
            return jnp.vdot(y.astype(jnp.float32), w.reshape(bt, s, -1)), y
        return jax.grad(loss, argnums=range(4), has_aux=True)(xbc, dt, a, d)

    (dxbc, ddt, da, dd), y = packed("kernel")
    want = _y_and_grads(lambda *t: ssd_scan(*t, backend="kernel"),
                        (x, dt, a, b, c, d), w)
    parts = jnp.split(dxbc, [x[0, 0].size, x[0, 0].size + b[0, 0].size], -1)
    for name, got, ref_ in zip(
            ("y",) + NAMES, (y, parts[0], ddt, da, parts[1], parts[2], dd),
            want):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32).ravel(),
            np.asarray(ref_, np.float32).ravel(), err_msg=name)
    (exbc, _, _, _), ey = packed("reference")
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    assert _rel(y, ey) < tol and _rel(dxbc, exbc) < tol


@pytest.mark.parametrize("shape,chunk", [
    pytest.param(dict(s=64, heads=2, p=64, n=128), 16, id="chunk_16"),
    pytest.param(dict(s=128, heads=2, p=64, n=64), 128, id="state_of_64"),
    pytest.param(dict(s=128, heads=2, p=64, g=2, n=128), 128,
                 id="a_group_64_lanes_wide"),
])
def test_shapes_the_kernels_do_not_take_fall_back(shape, chunk, monkeypatch):
    """Where the platform would take the kernels (here: forced interpret
    mode), auto still runs the einsum form for a chunk of 16, a state of
    64 and a group whose heads fill 64 lanes; pinned to the kernels such a
    shape is refused, not run wrongly."""
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    args, _ = _case(9, jnp.float32, **shape)
    lowered = str(jax.make_jaxpr(
        lambda *a: ssd_scan(*a, chunk=chunk))(*args))
    assert "pallas_call" not in lowered
    np.testing.assert_array_equal(
        ssd_scan(*args, chunk=chunk),
        ssd_scan(*args, chunk=chunk, backend="reference"))
    with pytest.raises(ValueError, match="multiples of 128"):
        ssd_scan(*args, chunk=chunk, backend="kernel")
    fits, _ = _case(9, jnp.float32, s=128)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: ssd_scan(*a))(*fits))


def test_backend_is_auto_kernel_or_reference():
    args, _ = _case(10, jnp.float32, s=128)
    with pytest.raises(ValueError, match="auto|kernel|reference"):
        ssd_scan(*args, backend="pallas")
    # off the TPU and without interpret mode, auto is the einsum form
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: ssd_scan(*a))(*args))
