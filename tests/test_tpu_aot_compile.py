"""Ask the TPU's compiler, without a TPU: the kernels of the main path at
GPT-2 125M widths, compiled for a DESCRIBED v5e chip.

Interpret mode cannot see what Mosaic refuses (block shapes, dots without
an M dimension, casts it lacks) — three serving kernels passed every
interpret-mode test and were refused on the chip.  Nothing runs here and
no time or result is measured; each test only asserts that the program
compiles and holds a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture that skips when
it cannot be: only one process at a time may load the TPU library, every
xdist worker imports every test file, so nothing here touches it while
the module is imported — and all such tests live in this ONE file, so
one worker holds the library.  ``default_backend`` is steered to "tpu"
for the module (the one resolver in ``ops/_pallas_utils.py`` asks it), in
the test, not through an option of the program.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from apex_tpu.models.config import gpt_125m

CFG = gpt_125m(max_position_embeddings=1024, remat=False,
               scan_layers=False, fused_head_ce=True)
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
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
def as_tpu(topo):
    """Route like a TPU process and keep the persistent compile cache out
    of it (a compile for a described chip is written there but cannot be
    read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    from apex_tpu.ops import _pallas_utils

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(_pallas_utils, "default_backend", lambda: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _like(tree, sharding, dtype=None):
    """Shapes of ``tree`` on ``sharding`` (floats as ``dtype`` if given)."""
    def leaf(x):
        dt = (dtype if dtype is not None
              and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype)
        return _spec(x.shape, dt, sharding)

    return jax.tree_util.tree_map(leaf, tree)


def _params(sharding, cfg=CFG):
    from apex_tpu.models.transformer_lm import init_gpt_params

    return _like(jax.eval_shape(lambda k: init_gpt_params(k, cfg),
                                jax.random.PRNGKey(0)), sharding)


def _flash(s):
    from apex_tpu.ops.flash_attention import flash_attention

    q = _spec((16, 1024, 12, 64), BF16, s)
    loss = lambda q, k, v: flash_attention(       # noqa: E731
        q, k, v, causal=True).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)


def _layer_norm(s):
    from apex_tpu.ops.layer_norm import fused_layer_norm

    x, w = _spec((16384, 768), BF16, s), _spec((768,), BF16, s)
    loss = lambda x, w, b: fused_layer_norm(      # noqa: E731
        x, w, b).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), (x, w, w)


def _decode(layout):
    def build(s):
        from apex_tpu.models.generate import decode_step, init_kv_cache

        cache = _like(jax.eval_shape(lambda: init_kv_cache(
            CFG, 8, 1024, cache_layout=layout)), s)
        # the default route: what ServingEngine resolves on a TPU
        return (lambda p, t, c: decode_step(p, t, c, CFG),
                (_params(s), _spec((8,), jnp.int32, s), cache))
    return build


def _sample(s):
    from apex_tpu.ops.fused_sampling import fused_sample

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return (lambda lg, k: fused_sample(lg, k, temperature=0.8, top_k=40,
                                       top_p=0.9),
            (_spec((8, 50304), jnp.float32, s),
             _spec(key.shape, key.dtype, s)))


def _quantized_matmul(s):
    from apex_tpu.ops.dense import quantize_weight, quantized_matmul

    w = _like(jax.eval_shape(lambda: quantize_weight(
        jnp.zeros((768, 3072), jnp.float32))), s)
    return quantized_matmul, (_spec((8, 768), BF16, s), w)


def _grouped_matmul(s):
    from apex_tpu.ops.grouped_matmul import grouped_matmul

    return grouped_matmul, (_spec((2048, 768), BF16, s),
                            _spec((8, 768, 3072), BF16, s),
                            _spec((9,), jnp.int32, s))


def _grouped_matmul_published(k, p):
    """The LFM2-24B-A2B cell's expert products (hidden 2048, expert width
    1536, SwiGLU: 2048 x 3072 and 1536 x 2048; 8 experts held, the
    worst-case buffer's chunk of 16,384 rows): forward, input gradient and
    weight gradient, three kernels."""
    def build(s):
        from apex_tpu.ops.grouped_matmul import grouped_matmul

        def fn(x, w, off):
            out, pull = jax.vjp(
                lambda x, w: grouped_matmul(x, w, off), x, w)
            return out, pull(out)

        return fn, (_spec((16384, k), BF16, s), _spec((8, k, p), BF16, s),
                    _spec((9,), jnp.int32, s))
    return build


def _prefill(s):
    from apex_tpu.models.generate import prefill

    return (lambda p, t: prefill(p, t, CFG, max_len=1024),
            (_params(s), _spec((4, 512), jnp.int32, s)))


@pytest.mark.parametrize("build", [
    pytest.param(_flash, id="flash_fwd_bwd"),
    pytest.param(_layer_norm, id="layer_norm_fwd_bwd"),
    pytest.param(_decode("contiguous"), id="decode_step_contiguous"),
    pytest.param(_decode("paged"), id="decode_step_paged"),
    pytest.param(_sample, id="fused_sample_topk_topp"),
    pytest.param(_quantized_matmul, id="quantized_matmul"),
    pytest.param(_grouped_matmul, id="grouped_matmul"),
    pytest.param(_grouped_matmul_published(2048, 3072),
                 id="grouped_matmul_fc1_2048x3072"),
    pytest.param(_grouped_matmul_published(1536, 2048),
                 id="grouped_matmul_fc2_1536x2048"),
    pytest.param(_prefill, id="prefill_4x512"),
])
def test_compiles_for_v5e(build, one_chip, as_tpu):
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kernels(text, scope):
    """The kernels' custom calls under ``scope`` (``/scope/`` in the
    ``op_name``, or ``jvp(scope)`` where the scope is outermost)."""
    import re

    word = re.compile(rf"[/(]{scope}[/)]")
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line
            and word.search(line)]


@pytest.mark.parametrize("b,s,heads,kv_heads,d", [
    pytest.param(8, 1024, 16, 16, 64, id="gpt_cell_b8s1024"),
    pytest.param(2, 8192, 32, 8, 64, id="lfm2_cell_b2s8192_gqa"),
    pytest.param(1, 8192, 32, 2, 128, id="nemotron_cell_b1s8192_gqa_d128"),
    pytest.param(2, 2048, 4, 4, 64, id="s2048"),
    pytest.param(1, 4096, 4, 2, 64, id="s4096_gqa"),
    pytest.param(1, 1024 + 40, 2, 2, 64, id="s1064_padded_tail"),
])
def test_causal_flash_kernels_compile(b, s, heads, kv_heads, d, one_chip,
                                      as_tpu):
    """The sub-tiled causal forward and both split backward kernels at
    the three causal cells' shapes (32 query heads over 2 K/V heads of
    128 among them: rep 16, one head a 128-lane block) and where the grid
    has tiles below, on and above the diagonal (every s >= 1024 takes
    1024 x 1024 tiles worked in 256-sided squares)."""
    from apex_tpu.ops.flash_attention import flash_attention

    q = _spec((b, s, heads, d), BF16, one_chip)
    k = _spec((b, s, kv_heads, d), BF16, one_chip)
    loss = lambda q, k, v: flash_attention(       # noqa: E731
        q, k, v, causal=True).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    for scope in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert len(_kernels(text, scope)) == 1, scope


def _attention_block(kind):
    """One attention block of a cell as ``_attention`` writes it
    (projection, flash attention, out projection), its configuration,
    batch and sequence."""
    from apex_tpu.models.config import lfm2_moe

    if kind == "lfm2":
        cfg = lfm2_moe(
            hidden_size=2048, num_hidden_layers=2,
            layer_types=["conv", "full_attention"],
            num_attention_heads=32, num_key_value_heads=8,
            intermediate_size=11776, moe_intermediate_size=1536,
            num_dense_layers=1, num_experts=64, num_experts_per_tok=4,
            vocab_size=8192, experts_held=(0, 8))
        return cfg, 2, 8192
    cfg = gpt_125m(num_layers=1, hidden_size=1024, num_attention_heads=16,
                   max_position_embeddings=1024, scan_layers=False)
    if kind == "bert":
        return dataclasses.replace(cfg, attn_mask_type="padding"), 8, 512
    return cfg, 8, 1024


def _layout_ops(text, b, s, elements):
    """The entry computation's ``copy`` / ``transpose`` instructions of
    an activation (``b`` leading, ``s`` among the axes) of more than
    ``elements`` elements whose ``op_name`` lies under ``core_attention``
    or ``qkv``."""
    import math
    import re

    entry = text[text.index("ENTRY"):]
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                     r"(copy|transpose)\(", line)
        if not m or not re.search(
                r'op_name="[^"]*[/(](core_attention|qkv)[/)]', line):
            continue
        dims = [int(x) for x in m.group(2).split(",") if x]
        if dims[0] == b and s in dims and math.prod(dims) > elements:
            found.append((m.group(1), line.strip()[:160]))
    return found


@pytest.mark.parametrize("kind", ["gpt", "bert", "lfm2"])
def test_attention_block_holds_no_activation_copy(kind, one_chip, as_tpu):
    """The counter of the kernels' layout, read at compile time: q, k, v,
    o and their gradients cross the flash kernels' boundary as ``[b, s,
    heads x d]``, the layout the projections on either side produce and
    consume, so forward and backward of a cell's attention block hold no
    ``copy`` or ``transpose`` of an activation-sized array (more than
    b x s x heads x d / 2 elements) under ``qkv`` or ``core_attention``
    (with ``[b x heads, s, d]`` kernels the GPT block held 9).  LFM2's
    q/k norm and rope sit between projection and kernel in the sequence-
    minor layout XLA gives them (``transformer_lm._sequence_minor``):
    what is left there is the chain's two ends, q in and dq out, and the
    assembly of the projection's gradient, in bfloat16; o, do and every
    float32 copy are gone."""
    from apex_tpu.models.transformer_lm import (
        _attention, init_gpt_params, rope_cos_sin, single_device_ctx)

    cfg, b, s = _attention_block(kind)
    layers = jax.eval_shape(lambda k: init_gpt_params(k, cfg),
                            jax.random.PRNGKey(0))["layers"]
    lp = layers[-1] if isinstance(layers, (list, tuple)) else {
        name: jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
        for name, x in layers.items()}
    lp = {name: x for name, x in lp.items() if name.split("_")[0] in (
        "qkv", "proj", "q", "k")}

    def loss(lp, x):
        rope = (rope_cos_sin(s, cfg.kv_channels, cfg.rope_theta)
                if cfg.qk_norm else None)
        return _attention(cfg, lp, x, single_device_ctx(), None, rope,
                          None).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _like(lp, one_chip, BF16),
        _spec((b, s, cfg.hidden_size), BF16, one_chip)).compile().as_text()
    scopes = (("flash_fwd", "flash_bwd") if kind == "bert" else
              ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    for scope in scopes:
        assert len(_kernels(text, scope)) == 1, scope
    heads_x_d = cfg.num_attention_heads * cfg.kv_channels
    found = _layout_ops(text, b, s, b * s * heads_x_d // 2)
    assert len(found) <= (3 if kind == "lfm2" else 0), found
    assert all(dtype == "bf16" for dtype, _ in found), found


def test_grouped_products_are_three_kernels(one_chip, as_tpu):
    """Forward, input gradient and weight gradient of a grouped product
    each compile to a kernel of their own name (no masked XLA product over
    all rows is left for the weight gradient)."""
    fn, args = _grouped_matmul_published(2048, 3072)(one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    for scope in ("gmm_fwd", "gmm_dx", "gmm_dw"):
        assert len(_kernels(text, scope)) == 1, scope


def test_lfm2_step_compiles_at_published_widths(one_chip, as_tpu):
    """The LFM2-24B-A2B hybrid stack at its published widths (hidden 2048,
    32 query heads over 8 K/V heads of 64, dense FFN 11776, experts of
    1536, 4 of 64 a token with 8 held) at the benchmark cell's b2 x s8192,
    cut to three layers (conv + dense FFN, attention + experts, conv +
    experts): the whole O2 train step compiles for the described v5e with
    every kernel in and fits the chip."""
    from apex_tpu.models.config import lfm2_moe
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = lfm2_moe(
        hidden_size=2048, num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"],
        num_attention_heads=32, num_key_value_heads=8,
        intermediate_size=11776, moe_intermediate_size=1536,
        num_dense_layers=1, num_experts=64, num_experts_per_tok=4,
        vocab_size=8192, experts_held=(0, 8), fused_head_ce=True,
        remat=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = _like(jax.eval_shape(
        init, jax.random.key_data(jax.random.key(0))), one_chip)
    ids = _spec((2, 8192), jnp.int32, one_chip)
    compiled = step.lower(state, ids, ids).compile()
    text = compiled.as_text()
    for scope in ("gmm_fwd", "gmm_dx", "gmm_dw", "flash_fwd",
                  "flash_bwd_dq", "flash_bwd_dkv"):
        assert _kernels(text, scope), scope
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) < 14e9


@pytest.mark.parametrize("pattern,parameters,fits_in", [
    ("EMEMEM*", 528_093_120, 13e9),
    ("MEMEM*EME", 666_963_456, 14.5e9),
], ids=["cell_seven_layers", "first_nine_layers"])
def test_nemotron_h_step_compiles_at_published_widths(
        pattern, parameters, fits_in, one_chip, as_tpu):
    """The benchmark's Nemotron-H cell as it is timed: published widths
    (hidden 2688, Mamba-2 mixers of 64 heads of 64 in 8 groups with a
    state of 128, experts of 1856 beside a shared expert of 3712, 6 of 128
    a token with 8 held, 32 query heads over 2 K/V heads of 128), the
    seven layers ``EMEMEM*`` at b1 x s8192.  The whole O2 train step
    compiles for the described v5e with the grouped and flash kernels in,
    and the compiler's byte count stays under the chip's 16 GB with room
    for the allocator: a change that runs the cell out of memory fails
    here, on the CPU, first.  The model's first nine layers ``MEMEM*EME``
    (ISSUE 34's cut; the check's reference, not the step, holds the cell
    to seven: PERF.md section 7) stay proven to fit too."""
    from apex_tpu.models.config import nemotron_h
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = nemotron_h(
        hidden_size=2688, num_hidden_layers=len(pattern),
        hybrid_override_pattern=pattern, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, mamba_num_heads=64,
        mamba_head_dim=64, ssm_state_size=128, n_groups=8, conv_kernel=4,
        chunk_size=128, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, n_routed_experts=128,
        num_experts_per_tok=6, routed_scaling_factor=2.5, vocab_size=16384,
        experts_held=(0, 8), fused_head_ce=True, remat=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = _like(jax.eval_shape(
        init, jax.random.key_data(jax.random.key(0))), one_chip)
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        state.master_params)) == parameters
    ids = _spec((1, 8192), jnp.int32, one_chip)
    compiled = step.lower(state, ids, ids).compile()
    text = compiled.as_text()
    for scope in ("gmm_fwd", "gmm_dx", "gmm_dw", "flash_fwd",
                  "flash_bwd_dq", "flash_bwd_dkv"):
        assert _kernels(text, scope), scope
    # the scan's kernels under the scope ``ssd_scan``: a Mamba layer runs
    # the forward twice (the layer's checkpoint recomputes it) and the
    # backward once, and nothing of the einsum form's intermediates is
    # left in HBM (its decay mask and scores: float32 [.., 128, 128] for
    # each of 64 chunks x 64 heads)
    mamba = pattern.count("M")
    scan = _kernels(text, "ssd_scan")
    assert sum("/ssd_fwd/" in line for line in scan) == 2 * mamba
    assert sum("/ssd_bwd/" in line for line in scan) == mamba
    assert not _score_buffers(text, "ssd_scan", 64 * 64 * 128 * 128)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) < fits_in


def _joyai_cell():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "joyai_llm_flash_ep32.json")) as f:
        return json.load(f)


def test_joyai_llm_flash_step_compiles_at_published_widths(one_chip,
                                                           as_tpu):
    """The benchmark's JoyAI-LLM-Flash cell as it is timed: published
    widths (hidden 2048, 32 heads of q/k 128 + 64 and v 128 through
    latents of 1536 and 512, dense FFN 7168, experts of 768 beside a gated
    shared expert, 8 of 256 a token with 8 held), layers 0-4 and the MTP
    module at b1 x s8192, three inputs.  The whole O2 train step compiles
    for the described v5e with the grouped kernels and the three flash
    kernels in: a block runs the forward kernel ONCE (its output and
    logsumexp are kept across the checkpoint) and each backward kernel
    once, six blocks; and nothing activation-sized is padded or broadcast
    around them: no bfloat16 array of q's or k's 32 x 192 lanes or of 32
    x 256 exists in the step."""
    import re

    from apex_tpu.models.config import joyai_llm_flash
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    config = _joyai_cell()
    cfg = joyai_llm_flash(**config["program"]["model_config_kwargs"])
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = _like(jax.eval_shape(
        init, jax.random.key_data(jax.random.key(0))), one_chip)
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        state.master_params)) == config["state_bytes"]["parameters"]
    ids = _spec((1, 8192), jnp.int32, one_chip)
    compiled = step.lower(state, ids, ids, ids).compile()
    text = compiled.as_text()
    for scope in ("gmm_fwd", "gmm_dx", "gmm_dw"):
        assert _kernels(text, scope), scope
    for scope in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert len(_kernels(text, scope)) == 6, scope
    # q or k at 192 a head (6144 lanes), either padded to 256 (8192 lanes
    # of q; kv_b's own product is split in two of 4096), the rotary key a
    # head (2048 lanes from a [.., 64] source is q_rope's own width: its
    # count is the rotary queries' alone)
    for lanes in (32 * 192, 32 * 256):
        assert not re.search(rf"bf16\[1,8192,{lanes}\]", text), lanes
        assert not re.search(rf"bf16\[8192,{lanes}\]", text), lanes
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) < 13e9


@pytest.mark.slow
def test_joyai_llm_flash_reference_fits_the_check(one_chip):
    """The check's float32 reference of the JoyAI-LLM-Flash cell, loss
    and gradients at b1 x s8192 in blocks (benchmark/reference/
    joyai_llm_flash.py), compiles for the described v5e, and what it
    takes beside its arguments and its gradients leaves room for the
    other float32 trees that benchmark/reference/train.py holds at its
    peak (6.25 trees of 492 M parameters: 12.3 GB of 15.75; 1.83 GB read,
    PR 37).  Marked slow: the compile alone is 100-200 s here."""
    from benchmark.reference import joyai_llm_flash as model
    from benchmark.reference import transformer as T

    config = _joyai_cell()
    params = _like(jax.eval_shape(
        lambda k: model.init_params(k, config), jax.random.key(0)),
        one_chip)
    ids = _spec((1, 8192), jnp.int32, one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda p, batch: model.loss(p, batch, config, T.Precision()))
    ).lower(params, (ids, ids, ids)).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2.5e9, m.temp_size_in_bytes


def _score_buffers(text, scope, elements):
    """The float32 shapes ``[.., 128, 128]`` of ``elements`` elements or
    more that an instruction under ``scope`` (any, if empty) produces or
    reads."""
    import math
    import re

    found = []
    for line in text.splitlines():
        if scope and f"/{scope}/" not in line:
            continue
        for dims in re.findall(r"f32\[([\d,]+)\]", line):
            shape = [int(d) for d in dims.split(",")]
            if shape[-2:] == [128, 128] and math.prod(shape) >= elements:
                found.append(shape)
    return found


def test_ssd_scan_kernels_compile_at_the_cell_shape(one_chip, as_tpu):
    """The state-space scan's forward, residual-producing forward and
    backward kernels at the Nemotron cell's shape (b1 x s8192, 64 heads of
    64 in 8 groups, state 128, chunk 128), on arrays of their own and on
    the mixer's one ``[x | B | C]``."""
    from apex_tpu.ops.ssd_scan import ssd_scan, ssd_scan_packed

    b, s, heads, p, g, n = 1, 8192, 64, 64, 8, 128
    x = _spec((b, s, heads, p), BF16, one_chip)
    dt = _spec((b, s, heads), jnp.float32, one_chip)
    a = _spec((heads,), jnp.float32, one_chip)
    bc = _spec((b, s, g, n), BF16, one_chip)
    loss = lambda *t: ssd_scan(*t).astype(jnp.float32).sum()  # noqa: E731
    text = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        x, dt, a, bc, bc, a).compile().as_text()
    assert len(_kernels(text, "ssd_fwd")) == 1
    assert len(_kernels(text, "ssd_bwd")) == 1
    assert not _score_buffers(text, "", 64 * 64 * 128 * 128)
    einsums = lambda *t: ssd_scan(                             # noqa: E731
        *t, backend="reference").astype(jnp.float32).sum()
    assert _score_buffers(
        jax.jit(einsums).lower(x, dt, a, bc, bc, a).compile().as_text(),
        "", 64 * 64 * 128 * 128)        # what the check is looking for
    xbc = _spec((b, s, heads * p + 2 * g * n), BF16, one_chip)
    packed = lambda *t: ssd_scan_packed(                       # noqa: E731
        *t, groups=g, state=n).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(packed, argnums=range(4))).lower(
        xbc, dt, a, a).compile().as_text()
    assert len(_kernels(text, "ssd_fwd")) == 1
    assert len(_kernels(text, "ssd_bwd")) == 1
    # forward alone: the kernel that keeps no states
    text = jax.jit(packed).lower(xbc, dt, a, a).compile().as_text()
    assert len(_kernels(text, "ssd_fwd")) == 1


def test_ddp_step_compiles_for_four_chips(topo, as_tpu):
    """``make_ddp_train_step`` over dp=4 with every kernel in: the
    parameter cotangents of the kernels' custom VJPs must type-check
    under shard_map.  Full width, depth cut to 2 layers (the 12-layer
    step compiles in ~90 s)."""
    from apex_tpu.models.transformer_lm import gpt_loss
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import create_mesh, make_ddp_train_step

    cfg = dataclasses.replace(CFG, num_layers=2)
    mesh = create_mesh(dp=4, devices=list(topo.devices))
    init, step = make_ddp_train_step(
        lambda p, t, l: gpt_loss(p, t, l, cfg), fused_adam(lr=1e-4),
        "O2", mesh, batch_axes=2)
    replicated = NamedSharding(mesh, P())
    state = _like(jax.eval_shape(init, _params(replicated, cfg)),
                  replicated)
    batch = _spec((16, 1024), jnp.int32, NamedSharding(mesh, P("dp")))
    compiled = jax.jit(step).lower(state, batch, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


def test_rematted_layer_runs_the_forward_flash_kernel_once(one_chip, as_tpu):
    """The benchmark's GPT cell (hidden 1024, 16 heads, b8 x s1024, remat
    + scan) cut to 2 layers: the layer's checkpoint keeps the forward
    kernel's output and logsumexp, so the backward loop holds the two
    backward kernels and no second forward kernel.  Only the chip's
    compiler can say what XLA and Mosaic make of the names."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = gpt_125m(num_layers=2, hidden_size=1024, num_attention_heads=16,
                   max_position_embeddings=1024, activation="gelu_tanh",
                   fused_head_ce=True, remat=True, scan_layers=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = _like(jax.eval_shape(
        init, jax.random.key_data(jax.random.key(0))), one_chip)
    ids = _spec((8, 1024), jnp.int32, one_chip)
    text = step.lower(state, ids, ids).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]

    def count(scope):
        return sum(f"/{scope}/" in line for line in kernels)

    assert count("flash_fwd") == 1
    assert count("flash_bwd_dq") == 1 and count("flash_bwd_dkv") == 1
