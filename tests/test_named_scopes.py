"""The jitted train step carries the program's ``jax.named_scope`` words on
every instruction that does work (docs/observability.md, "Scopes inside the
jitted step"): ``benchmark/scope_times.py`` splits a chip trace's device time
by them, so a matmul or kernel that loses its scope goes dark there.

Toy sizes on the CPU with the kernels interpreted, so that the Pallas routes
(and their scopes) are taken.  The compiled text is read, not the lowered
one: a scanned or called body's ``op_name`` gets its caller's prefix
(``jvp(model)/backbone/while/body/...``) only once the call is inlined.
"""

import re

import jax
import numpy as np
import pytest

from apex_tpu.models.bert import make_bert_train_step
from apex_tpu.models.config import (
    bert_large, gpt_125m, lfm2_moe, nemotron_h)
from apex_tpu.models.gpt import make_gpt_train_step
from apex_tpu.optimizers import fused_adam, fused_lamb

TOY = dict(num_layers=2, hidden_size=128, num_attention_heads=4,
           vocab_size=512, max_position_embeddings=64)
B, S = 2, 64

LAYER = {"ln1", "attention", "qkv", "core_attention", "proj", "ln2", "mlp",
         "fc1", "fc2"}
# forward and backward of the same scope; ``residual`` is an add, whose
# transpose is no instruction
BOTH_WAYS = LAYER | {"embed", "final_ln", "cast_params"}
STEP = {"amp_unscale", "amp_scale_update", "optimizer", "apply_update",
        "cast_params"}
KERNELS_FWD = {"flash_fwd", "layer_norm_fwd"}
KERNELS_BWD = {"flash_bwd", "layer_norm_bwd"}      # toy s64: fused backward
# what the backward needs again of a rematted layer (fc2's output and the
# projection's are not among it, their inputs are).  Not the flash kernel:
# the layer's checkpoint keeps its output and logsumexp, so neither the
# kernel nor the layout copies that fed it run a second time
RECOMPUTED = {"ln1", "qkv", "ln2", "fc1", "layer_norm_fwd"}
NOT_RECOMPUTED = {"flash_fwd", "core_attention"}
# the hybrid stack's own (models/hybrid.py, transformer/moe.py,
# ops/grouped_matmul.py): forward and backward alike, but for the kernels
HYBRID = {"short_conv", "conv_in", "conv_gate", "conv_out", "qk_norm",
          "rope", "router", "moe_dispatch", "expert_ffn", "moe_combine",
          "dense_ffn"}
# a stack of single mixers (Nemotron-H): no ``ln2``, no dense FFN
MIXERS = {"mamba_mixer", "ssm_in", "ssm_conv", "ssd_scan", "ssm_gate_norm",
          "ssm_out", "shared_expert", "router", "moe_dispatch",
          "expert_ffn", "moe_combine"}
HYBRID_KERNELS_FWD = {"gmm_fwd"}
HYBRID_KERNELS_BWD = {"gmm_dx", "gmm_dw"}
ALL = (BOTH_WAYS | STEP | KERNELS_FWD | KERNELS_BWD
       | {"residual", "lm_head_ce", "embedding_ln", "mlm_head", "nsp_head",
          "trust_ratio", "flash_bwd_dq", "flash_bwd_dkv", "grad_reduce"}
       | HYBRID | MIXERS | HYBRID_KERNELS_FWD | HYBRID_KERNELS_BWD)


def _gpt():
    cfg = gpt_125m(**TOY, activation="gelu_tanh", fused_head_ce=True,
                   remat=True, scan_layers=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    ids = np.zeros((B, S), np.int32)
    return init, step, (ids, ids), {"lm_head_ce"}, set(), True, set()


def _bert():
    cfg = bert_large(**TOY, remat=False, scan_layers=False)
    init, step = make_bert_train_step(
        cfg, fused_lamb(lr=1e-4, weight_decay=0.01), "O2")
    ids = np.zeros((B, S), np.int32)
    batch = (ids, ids, np.zeros((B,), np.int32), ids, np.ones_like(ids))
    return (init, step, batch, {"embedding_ln", "mlm_head", "nsp_head"},
            {"trust_ratio"}, False, set())


def _lfm2():
    """The LFM2-MoE pattern at toy widths: a conv layer with a dense FFN,
    then attention, conv, conv, conv layers with experts (4 of 16 held),
    each layer its own checkpoint."""
    cfg = lfm2_moe(
        hidden_size=128, num_hidden_layers=5,
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=256, moe_intermediate_size=128,
        num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
        vocab_size=512, experts_held=(4, 4), fused_head_ce=True,
        remat=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    ids = np.zeros((B, S), np.int32)
    return init, step, (ids, ids), {"lm_head_ce"}, set(), True, HYBRID


def _nemotron():
    """The Nemotron-H pattern at toy widths: expert layers (4 of 16 held,
    beside a shared expert), Mamba-2 mixers and one attention layer, one
    mixer a layer, each layer its own checkpoint."""
    cfg = nemotron_h(
        hidden_size=128, num_hidden_layers=4, hybrid_override_pattern="EM*M",
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
        conv_kernel=4, chunk_size=16, moe_intermediate_size=128,
        moe_shared_expert_intermediate_size=128, n_routed_experts=16,
        num_experts_per_tok=6, routed_scaling_factor=2.5, vocab_size=512,
        experts_held=(4, 4), fused_head_ce=True, remat=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    ids = np.zeros((B, S), np.int32)
    return init, step, (ids, ids), {"lm_head_ce"}, set(), True, MIXERS


def _words(op_name: str) -> set:
    """The scope words of an ``op_name``: its parts less the transforms
    around them (``transpose(jvp(model))`` -> ``model``)."""
    return {re.sub(r"^(?:\w+\()+|\)+$", "", part)
            for part in op_name.split("/")}


@pytest.mark.parametrize("build", [_gpt, _bert, _lfm2, _nemotron],
                         ids=["gpt_scan_remat", "bert_unrolled",
                              "lfm2_hybrid_remat", "nemotron_mixers_remat"])
def test_every_part_of_the_step_is_scoped(build, monkeypatch):
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    init, step, batch, heads, optimizer_words, remat, hybrid = build()
    state = jax.eval_shape(init, jax.random.key_data(jax.random.key(0)))
    text = step.lower(state, *batch).compile().as_text()

    seen = {"forward": set(), "recompute": set(), "backward": set(),
            "update": set()}
    unscoped_work = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([\w\-]+)\(", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        op_name = op.group(1) if op else ""
        words = _words(op_name) & ALL
        if "rematted_computation" in op_name:
            seen["recompute"] |= words
        elif "transpose(" in op_name:
            seen["backward"] |= words
        elif "jvp(" in op_name:
            seen["forward"] |= words
        else:
            seen["update"] |= words
        if m.group(2) in ("dot", "convolution", "custom-call") and not words:
            unscoped_work.append(line.strip()[:200])

    # (a) forward, backward and, with remat, recomputed forward; a stack
    # of single mixers has one norm a layer and no dense FFN
    absent = {"ln2", "fc1", "fc2"} if hybrid is MIXERS else set()
    assert (BOTH_WAYS | heads | KERNELS_FWD | {"residual"}) - absent <= seen[
        "forward"]
    assert (BOTH_WAYS | heads | KERNELS_BWD) - absent <= seen["backward"]
    if remat:
        assert RECOMPUTED - absent <= seen["recompute"]
        assert not NOT_RECOMPUTED & seen["recompute"]
    else:
        assert not seen["recompute"]
    assert not absent & set().union(*seen.values())
    if hybrid:
        assert hybrid | HYBRID_KERNELS_FWD <= seen["forward"]
        assert hybrid | HYBRID_KERNELS_BWD <= seen["backward"]
        # the expert layer is recomputed with the rest of its layer, and
        # so are the mixer and its scan
        own = ({"ssm_in", "ssd_scan", "shared_expert"} if hybrid is MIXERS
               else {"conv_in"})
        assert {"router", "expert_ffn", "gmm_fwd"} | own <= seen[
            "recompute"]
    # (b) no matmul or kernel without a word of the program's
    assert not unscoped_work
    # (c) the step's own phases, outside the differentiated function
    assert STEP - {"cast_params"} | optimizer_words <= seen["update"]
    assert "cast_params" in seen["update"]
    assert not (STEP - {"cast_params"}) & (seen["forward"] | seen["backward"])


def test_the_scan_kernels_are_named_under_ssd_scan(monkeypatch):
    """At a shape the state-space scan's kernels take (2 heads of 64 in one
    group, a state of 128, chunk 128) the Mamba-2 mixer's scope
    ``ssd_scan`` holds the kernels ``ssd_fwd`` (forward, and again where
    the layer's checkpoint recomputes it) and ``ssd_bwd`` (backward): the
    innermost word of the benchmark's ``scope_words`` in their
    ``op_name`` is ``ssd_scan``, which ``ssd_scan_ms`` and
    ``ssd_scan_roofline`` read."""
    from apex_tpu.models import hybrid

    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    cfg = nemotron_h(
        hidden_size=128, num_hidden_layers=1, hybrid_override_pattern="M",
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        mamba_num_heads=2, mamba_head_dim=64, ssm_state_size=128, n_groups=1,
        conv_kernel=4, chunk_size=128, moe_intermediate_size=128,
        moe_shared_expert_intermediate_size=128, n_routed_experts=16,
        num_experts_per_tok=6, routed_scaling_factor=2.5, vocab_size=512)
    lp = hybrid.init_hybrid_params(jax.random.key(0), cfg)["layers"][0]
    u = np.zeros((1, 128, 128), np.float32)

    def loss(lp, u):
        with jax.named_scope("mamba_mixer"):
            return hybrid.mamba_mixer(cfg, lp, u).sum()

    text = jax.jit(jax.grad(jax.checkpoint(loss))).lower(
        lp, u).compile().as_text()
    # (interpret mode hoists a constant or two out of a kernel's loop
    # under the kernel's bare name)
    names = re.findall(r'op_name="(jit[^"]*ssd_[fb]wd[^"]*)"', text)
    assert names and all("/mamba_mixer/ssd_scan/" in n for n in names)
    fwd = [n for n in names if "/ssd_fwd/" in n]
    bwd = [n for n in names if "/ssd_bwd/" in n]
    assert fwd and all("rematted_computation" in n for n in fwd)
    assert bwd and all("transpose(" in n
                       and "rematted_computation" not in n for n in bwd)


MLA = {"mla_attention", "mla_q_latent", "mla_kv_latent", "mla_rope",
       "mla_out"}
MTP = {"mtp", "mtp_merge", "mtp_head"}


@pytest.mark.parametrize("route", ["kernels", "reference"])
def test_the_latent_stack_and_the_mtp_module_are_scoped(route, monkeypatch):
    """The DeepSeek-V3 layer (one dense layer, one expert layer, the MTP
    module; at the kernels' head widths, 128 + 64 and v 128, or at toy
    widths, where ``mha_reference`` runs): every product and kernel has a
    word; the latent block's parts and the module's are there forward,
    recomputed and backward; the flash kernels keep their names and the
    forward one is not recomputed; the module's block carries ``mtp``
    OUTSIDE its ``mla_attention``, which is what lets the benchmark read
    ``mtp_ms`` and ``mla_attention_ms`` as everything under a word."""
    from apex_tpu.models.config import joyai_llm_flash

    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    wide = route == "kernels"
    cfg = joyai_llm_flash(
        hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
        q_lora_rank=64, kv_lora_rank=32,
        qk_nope_head_dim=128 if wide else 32,
        qk_rope_head_dim=64 if wide else 16,
        v_head_dim=128 if wide else 32, intermediate_size=256,
        moe_intermediate_size=128, first_k_dense_replace=1,
        n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
        routed_scaling_factor=2.5, vocab_size=512,
        num_nextn_predict_layers=1, experts_held=(4, 4),
        fused_head_ce=True, remat=True)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = jax.eval_shape(init, jax.random.key_data(jax.random.key(0)))
    ids = np.zeros((B, S), np.int32)
    text = step.lower(state, ids, ids, ids).compile().as_text()

    known = (ALL | HYBRID | MLA | MTP | HYBRID_KERNELS_FWD
             | HYBRID_KERNELS_BWD | {"shared_expert", "mtp_norm"})
    seen = {"forward": set(), "recompute": set(), "backward": set()}
    unscoped_work, nested = [], set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([\w\-]+)\(", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not m or not op:
            continue
        words = _words(op.group(1)) & known
        if "rematted_computation" in op.group(1):
            seen["recompute"] |= words
        elif "transpose(" in op.group(1):
            seen["backward"] |= words
        elif "jvp(" in op.group(1):
            seen["forward"] |= words
        if "mtp" in words:
            nested |= words
        if m.group(2) in ("dot", "convolution", "custom-call") and not words:
            unscoped_work.append(line.strip()[:200])
    assert not unscoped_work
    block = MLA | {"core_attention", "ln1", "ln2", "dense_ffn", "router",
                   "expert_ffn", "shared_expert", "moe_dispatch",
                   "moe_combine"}
    assert block | MTP | {"gmm_fwd", "lm_head_ce"} <= seen["forward"]
    assert block | MTP | {"gmm_dx", "gmm_dw"} <= seen["backward"]
    # the backward needs the output projection's input again, not its
    # output
    assert (MLA - {"mla_out"} | {"shared_expert", "router", "gmm_fwd",
                                 "mtp"} <= seen["recompute"])
    assert "attention" not in set().union(*seen.values())
    # the module's own block and head, under its word
    assert MLA | {"router", "expert_ffn", "shared_expert", "mtp_head",
                  "mtp_merge", "mtp_norm"} <= nested
    assert "lm_head_ce" not in nested and "dense_ffn" not in nested
    kernels = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    if wide:
        assert "flash_fwd" in seen["forward"]
        assert {"flash_bwd_dq", "flash_bwd_dkv"} <= seen["backward"]
        assert "flash_fwd" not in seen["recompute"]
    else:
        assert not kernels & set().union(*seen.values())
