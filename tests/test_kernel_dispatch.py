"""Which implementation of an op runs is decided in one place
(``ops/_pallas_utils.py``): from the platform, from interpret mode, from
the caller's ``backend=`` and, inside flash attention, from the static
shape.  Nothing here runs a kernel: an op is traced, and either its own
kernel's driver was called or it was not (the fused decode layer's
reference holds paged attention's kernel, so a ``pallas_call`` in the
trace would not tell).
"""

import ast
import pathlib

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.ops import (
    _pallas_utils, decode_step, dense, fused_sampling, grouped_matmul as gmm,
    paged_attention)
from apex_tpu.ops import flash_attention as fa

OPS_DIR = pathlib.Path(_pallas_utils.__file__).parent


def _paged():
    q = jnp.zeros((2, 4, 64))
    pool = jnp.zeros((4, 8, 2, 64))
    return q, pool, pool, jnp.zeros((2, 2), jnp.int32), jnp.ones(
        (2,), jnp.int32)


def _decode_layer(backend):
    return decode_step.fused_decode_layer(
        *_paged(), jnp.zeros((4 * 64, 128)), backend=backend)


def _grouped(backend):
    return gmm.grouped_matmul(
        jnp.zeros((24, 16)), jnp.zeros((4, 16, 8)),
        jnp.asarray([0, 3, 3, 20, 24], jnp.int32), backend=backend)


def _grouped_quantized(backend):
    w = gmm.quantize_group_weights(jnp.ones((3, 64, 48)), block=16)
    return gmm.grouped_matmul_quantized(
        jnp.zeros((40, 64)), w["wire"], w["scale"],
        jnp.asarray([0, 12, 12, 40], jnp.int32), backend=backend)


def _dense_quantized(backend):
    w = dense.quantize_weight(jnp.ones((32, 8)))
    return dense.dense_quantized(jnp.zeros((4, 32)), w["wire"], w["scale"],
                                 backend=backend)


# the name resolve_backend is given: (call, the kernel's driver)
OPS = {
    "paged attention": (
        lambda backend: paged_attention.ragged_paged_attention(
            *_paged(), backend=backend),
        (paged_attention, "_paged_pallas")),
    "grouped_matmul": (_grouped, (gmm, "_gmm_pallas")),
    "fused sampling": (
        lambda backend: fused_sampling.fused_sample(
            jnp.zeros((2, 256)), jax.random.PRNGKey(0), temperature=1.0,
            backend=backend),
        (fused_sampling, "_fused_pallas")),
    "fused decode layer": (_decode_layer, (decode_step, "_fused_pallas")),
    "quantized matmul": (_dense_quantized, (dense, "_dq_pallas")),
    "quantized grouped_matmul": (_grouped_quantized, (gmm, "_gmm_pallas")),
}


def _ran(op, backend, monkeypatch):
    """``"kernel"`` if tracing the op called its kernel's driver."""
    call, (module, driver) = OPS[op]
    real, calls = getattr(module, driver), []

    def spy(*args, **kw):
        calls.append(driver)
        return real(*args, **kw)

    monkeypatch.setattr(module, driver, spy)
    jax.make_jaxpr(lambda: call(backend))()
    return "kernel" if calls else "reference"


@pytest.mark.parametrize("backend,interpret,want", [
    pytest.param(None, False, "reference", id="auto_on_the_cpu"),
    pytest.param(None, True, "kernel", id="auto_in_interpret_mode"),
    pytest.param("auto", True, "kernel", id="auto_by_name"),
    pytest.param("kernel", False, "kernel", id="kernel_pinned"),
    pytest.param("reference", True, "reference", id="reference_pinned"),
    pytest.param("fast", True, ValueError, id="bad_value_raises"),
])
@pytest.mark.parametrize("op", sorted(OPS))
def test_backend_resolution(op, backend, interpret, want, monkeypatch):
    if interpret:
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET", raising=False)
    if want is ValueError:
        with pytest.raises(ValueError, match=f"{op}: backend='fast'"):
            _ran(op, backend, monkeypatch)
    else:
        assert _ran(op, backend, monkeypatch) == want


@pytest.mark.parametrize("last_dim,dtype,interpret,want", [
    (256, jnp.bfloat16, False, False),     # the CPU, no interpret mode
    (256, jnp.bfloat16, True, True),
    (200, jnp.bfloat16, True, False),      # not lane-aligned
    (256, jnp.int32, True, False),
])
def test_row_kernel_gate(last_dim, dtype, interpret, want, monkeypatch):
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1" if interpret else "0")
    assert _pallas_utils.pallas_ok(last_dim, dtype) is want


@pytest.mark.parametrize("sq,sk,want", [
    # the benchmark's cells (heads and batch take no part)
    pytest.param(512, 512, ("fused", 512), id="bert_b8s512"),
    pytest.param(1024, 1024, ("split", None), id="gpt_b8s1024"),
    pytest.param(8192, 8192, ("split", None), id="lfm2_b2s8192"),
    # 640 queries pad to 768, which 512 does not divide: the grid's tile
    pytest.param(640, 512, ("fused", 256), id="padded_768_queries"),
    pytest.param(128, 128, ("fused", 128), id="shorter_than_a_block"),
    pytest.param(512, 640, ("split", None), id="keys_pad_past_512"),
])
def test_flash_backward_plan_follows_the_static_shape(sq, sk, want):
    block_q, block_k = fa._blocks(sq, sk)
    sqp = -(-sq // block_q) * block_q
    skp = -(-sk // block_k) * block_k
    plan = fa._bwd_plan(sqp, skp, block_q)
    assert plan == want
    if plan[0] == "fused":
        assert sqp % plan[1] == 0


def test_the_ops_read_the_environment_once():
    """One mention of ``os.environ`` / ``os.getenv`` under ``ops/``, the
    read of ``APEX_TPU_PALLAS_INTERPRET``; nothing imports them by name;
    no ``_route`` of an op's own is left."""
    mentions, variables = [], []
    for path in sorted(OPS_DIR.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.FunctionDef):
                assert node.name != "_route", path.name
            if isinstance(node, ast.ImportFrom) and node.module == "os":
                assert not {"environ", "getenv"} & {
                    a.name for a in node.names}, path.name
            if isinstance(node, ast.Attribute) and ast.unparse(node) in (
                    "os.environ", "os.getenv"):
                mentions.append(path.name)
            if isinstance(node, ast.Call) and ast.unparse(node.func) in (
                    "os.environ.get", "os.getenv"):
                variables.append(ast.literal_eval(node.args[0]))
    assert mentions == ["_pallas_utils.py"]
    assert variables == ["APEX_TPU_PALLAS_INTERPRET"]
