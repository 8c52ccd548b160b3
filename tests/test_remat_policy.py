"""``TransformerConfig.remat`` keeps flash attention's output and logsumexp.

A rematted layer is recomputed in the backward pass except for the two
arrays that ``ops/flash_attention._flash_fwd`` tags with
``checkpoint_name`` (``REMAT_SAVED_NAMES``): the forward kernel runs once
a layer, not twice, and the numbers do not change.  Toy sizes on the CPU,
kernels interpreted; what the chip's compiler makes of it is asked in
``tests/test_tpu_aot_compile.py``.
"""

import re

import jax
import numpy as np
import pytest

from apex_tpu.models.config import gpt_125m
from apex_tpu.models.transformer_lm import gpt_loss, init_gpt_params
from apex_tpu.ops import flash_attention as fa

FLASH = ("flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv")


def _cfg(s=64, width=128, **kw):
    kw = dict(remat=True, scan_layers=True) | kw
    return gpt_125m(num_layers=2, hidden_size=width,
                    num_attention_heads=width // 32, vocab_size=4 * width,
                    max_position_embeddings=s, **kw)


def _grad_fn(cfg, rng=None):
    return jax.grad(
        lambda p, ids: gpt_loss(p, ids, ids, cfg, dropout_rng=rng))


def _inputs(cfg, b=2):
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1),
                             (b, cfg.max_position_embeddings), 0,
                             cfg.vocab_size)
    return params, ids


def _flash_kernels(jaxpr, found=None):
    """The scope of every flash ``pallas_call`` equation in ``jaxpr`` and
    the jaxprs inside it (scan and checkpoint bodies), in order."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            scope = str(eqn.source_info.name_stack).rsplit("/", 1)[-1]
            if scope in FLASH:
                found.append(scope)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _flash_kernels(sub, found)
    return found


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")


def _drop_policy(mp):
    """``jax.checkpoint`` without its policy: the layer as it was
    rematted before the names, everything recomputed."""
    real = jax.checkpoint
    mp.setattr(jax, "checkpoint",
               lambda fn, **kw: real(fn, **(kw | {"policy": None})))


@pytest.mark.parametrize("plain", [False, True],
                         ids=["names_kept", "plain_checkpoint"])
@pytest.mark.parametrize("s, bwd, kernels", [
    pytest.param(64, None, ["flash_fwd", "flash_bwd"], id="fused_s64"),
    pytest.param(64, "split", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"],
                 id="split_pinned_s64"),
    pytest.param(1024, None, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"],
                 id="split_s1024"),
])
def test_forward_kernel_is_traced_once(s, bwd, kernels, plain, interpreted,
                                       monkeypatch, flash_bwd):
    """One forward kernel and the backward ones in the whole gradient;
    the plain checkpoint is the control, with the forward kernel again
    in its rematted body."""
    if bwd:
        flash_bwd(bwd)
    if plain:
        _drop_policy(monkeypatch)
    cfg = _cfg(s)
    params, ids = _inputs(cfg, b=1)
    found = _flash_kernels(jax.make_jaxpr(_grad_fn(cfg))(params, ids).jaxpr)
    assert sorted(found) == sorted(kernels + ["flash_fwd"] * plain)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
def test_gradients_equal_plain_checkpoint_to_the_bit(dropout, scan,
                                                     interpreted):
    """The saved ``o`` is the dropped-out output and the backward kernels
    draw the mask again from the seed: nothing is computed differently,
    one kernel run is left out.  Op by op: compiled as a whole for the
    CPU, the interpreted kernel is fused with its neighbours, otherwise
    in the step that holds it twice (8 of 98,304 elements one ulp off)."""
    cfg = _cfg(32, 64, attention_dropout=dropout, scan_layers=scan)
    rng = jax.random.PRNGKey(7) if dropout else None
    params, ids = _inputs(cfg, b=1)
    with jax.disable_jit():
        kept = _grad_fn(cfg, rng)(params, ids)
        with pytest.MonkeyPatch.context() as mp:
            _drop_policy(mp)
            plain = _grad_fn(cfg, rng)(params, ids)
    leaves = jax.tree_util.tree_leaves_with_path(kept)
    assert any(np.any(np.asarray(leaf)) for _, leaf in leaves)
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_dropout_mask_is_drawn(interpreted):
    """The bit-equality above is no comparison of two steps without
    dropout: the gradient under attention dropout 0.1 is another."""
    grads = []
    for p_drop in (0.0, 0.1):
        cfg = _cfg(32, 64, attention_dropout=p_drop)
        params, ids = _inputs(cfg, b=1)
        grads.append(jax.jit(_grad_fn(cfg, jax.random.PRNGKey(7)))(
            params, ids)["layers"]["qkv_kernel"])
    assert not np.array_equal(np.asarray(grads[0]), np.asarray(grads[1]))


def _lowered(cfg):
    """The step's StableHLO (no locations in it), its private functions
    renamed by first appearance: their numbers count up as jax lowers."""
    params, ids = _inputs(cfg)
    text = jax.jit(_grad_fn(cfg)).lower(params, ids).as_text()
    order = {}
    return re.sub(r"@\w+", lambda m: order.setdefault(
        m.group(), f"@f{len(order)}"), text)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
def test_without_remat_the_names_lower_to_nothing(scan, interpreted,
                                                  monkeypatch):
    """Outside a checkpoint the tag is an identity: the BERT cell's step
    (``remat=False``), serving and ring attention lower to what they
    lowered to before it."""
    cfg = _cfg(remat=False, scan_layers=scan)
    tagged = _lowered(cfg)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert _lowered(cfg) == tagged
