"""Mixed-precision policies: the O0–O5 opt levels as explicit dtype policy.

The reference encodes each opt level as a ``Properties`` object with validated
``__setattr__`` (apex/amp/frontend.py:8-114) consumed by ``_initialize`` to
cast the model and patch optimizers. Under jit there is nothing to patch:
a policy here is three dtypes plus flags, applied functionally at train-step
boundaries. Semantics per level follow frontend.py:119-255:

====  ===========  =============  ==========  ==============  ===========
lvl   param dtype  compute dtype  bn fp32     master weights  loss scale
====  ===========  =============  ==========  ==============  ===========
O0    fp32         fp32           n/a         no              1.0
O1    fp32         fp16 (listed)  yes         no              dynamic
O2    fp16         fp16           yes         yes             dynamic
O3    fp16         fp16           no          no              1.0
O4    fp32         bf16 (listed)  yes         no              1.0
O5    bf16         bf16           yes         yes             1.0
====  ===========  =============  ==========  ==============  ===========

(bf16 levels need no loss scaling — same exponent range as fp32.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp

__all__ = [
    "Properties",
    "Policy",
    "O0",
    "O1",
    "O2",
    "O3",
    "O4",
    "O5",
    "opt_levels",
    "policy_for_opt_level",
]


_ALLOWED_KEYS = {
    "enabled",
    "opt_level",
    "cast_model_type",
    "patch_functions",
    "patch_functions_type",
    "keep_batchnorm_fp32",
    "master_weights",
    "loss_scale",
}


class Properties:
    """Validated bag of amp options (reference frontend.py:8-114).

    Unknown attribute assignment raises, matching the reference's guard
    against typos in ``amp.initialize(..., **kwargs)`` overrides.
    """

    def __init__(self, **kwargs):
        object.__setattr__(self, "_data", dict(
            enabled=False,
            opt_level=None,
            cast_model_type=None,
            patch_functions=False,
            patch_functions_type=None,
            keep_batchnorm_fp32=None,
            master_weights=None,
            loss_scale=1.0,
        ))
        for k, v in kwargs.items():
            setattr(self, k, v)

    def __getattr__(self, name):
        data = object.__getattribute__(self, "_data")
        if name in data:
            return data[name]
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name not in _ALLOWED_KEYS:
            raise AttributeError(
                f"{name!r} is not an amp option; allowed: {sorted(_ALLOWED_KEYS)}"
            )
        if name == "loss_scale" and not (
            value == "dynamic" or isinstance(value, (int, float))
        ):
            raise ValueError("loss_scale must be 'dynamic' or a number")
        object.__getattribute__(self, "_data")[name] = value

    def _asdict(self):
        return dict(object.__getattribute__(self, "_data"))

    def __repr__(self):
        return f"amp.Properties({self._asdict()})"


# a state-space mixer's per-head constants (models/hybrid.py): the decay
# ``exp(dt * -exp(A_log))`` multiplies a state over thousands of positions,
# so ``A_log``, ``dt_bias`` and ``D`` stay float32 wherever the norms'
# scales do (Mamba-2 keeps them so under mixed precision)
_FP32_CONSTANTS = ("ssm_a_log", "ssm_dt_bias", "ssm_d")


def _is_norm_param(path: tuple) -> bool:
    """Heuristic: does this param path belong to a normalization layer?

    Used for ``keep_batchnorm_fp32`` — the reference special-cases
    ``nn.modules.batchnorm._BatchNorm`` during the model cast
    (apex/amp/_initialize.py:178-184, fp16_utils ``convert_network``).
    In a pytree we go by path naming, which matches flax's
    BatchNorm/LayerNorm/GroupNorm module naming conventions.  The leaves
    named in ``_FP32_CONSTANTS`` are kept with them, and ``norm`` in a
    name also keeps a latent-attention layer's two latent norms
    (``q_a_norm_scale``, ``kv_a_norm_scale``) and a multi-token-prediction
    module's three (models/hybrid.py).
    """
    keywords = ("batchnorm", "batch_norm", "bn", "layernorm", "layer_norm",
                "groupnorm", "group_norm", "norm")
    for key in path:
        name = getattr(key, "key", getattr(key, "name", str(key)))
        low = str(name).lower()
        if any(k in low for k in keywords) or low in _FP32_CONSTANTS:
            return True
    return False


def _effective(dtype):
    """Map fp16 → bf16 when running on TPU.

    TPUs have no native float16 — XLA emulates it, and the rounding behavior
    is fusion-dependent (verified on v5e: the same fp16 matmul backward
    yields ``-inf`` eagerly but large-finite values under jit). A TPU-native
    AMP therefore realizes the fp16 opt levels (O1/O2/O3) in bfloat16, which
    the MXU supports natively — the same reasoning that led the reference to
    add bf16 levels O4/O5 for ROCm (frontend.py:212-255). Dynamic loss
    scaling is kept for semantic parity (it simply never triggers in bf16's
    fp32-equal exponent range). Set ``APEX_TPU_ALLOW_FP16=1`` to force true
    (emulated, unreliable) fp16 on TPU.
    """
    import os

    if dtype == jnp.float16 and os.environ.get("APEX_TPU_ALLOW_FP16") != "1":
        from apex_tpu.ops._pallas_utils import on_tpu

        if on_tpu():
            return jnp.bfloat16
    return dtype


@dataclasses.dataclass(frozen=True)
class Policy:
    """Functional dtype policy: what dtype params, compute, and outputs use."""

    opt_level: str = "O0"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32
    keep_norm_fp32: bool = False
    master_weights: bool = False
    loss_scale: Union[str, float] = 1.0
    # O1/O4 express per-op casting (cast-listed functions run in
    # compute_dtype, blacklisted ones in fp32) rather than casting params.
    per_op_casts: bool = False
    norm_predicate: Callable[[tuple], bool] = _is_norm_param

    # ---- pytree casting helpers -------------------------------------------

    def _cast_tree(self, tree, dtype, respect_norms: bool):
        dtype = _effective(dtype)
        def cast_leaf(path, x):
            if not hasattr(x, "dtype") or not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            if respect_norms and self.keep_norm_fp32 and self.norm_predicate(path):
                return x.astype(jnp.float32)
            return x.astype(dtype)

        return jax.tree_util.tree_map_with_path(cast_leaf, tree)

    def cast_params(self, params):
        """Model-storage cast (reference ``model.to(cast_model_type)``)."""
        return self._cast_tree(params, self.param_dtype, respect_norms=True)

    def cast_to_compute(self, tree, respect_norms: bool = False):
        """Cast activations/inputs to the compute dtype (forward-patch
        analog, reference _initialize.py:196-203). Pass
        ``respect_norms=True`` when casting *params* so ``keep_norm_fp32``
        survives (O1/O4 keep norm-layer params fp32)."""
        return self._cast_tree(tree, self.compute_dtype, respect_norms)

    def cast_to_output(self, tree):
        return self._cast_tree(tree, self.output_dtype, respect_norms=False)

    def cast_master(self, params):
        """fp32 master copy for the optimizer (reference
        _process_optimizer.py:28-91 ``lazy_init_with_master_weights``)."""
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            params,
        )

    @property
    def uses_loss_scaling(self) -> bool:
        return self.loss_scale == "dynamic" or (
            isinstance(self.loss_scale, (int, float)) and self.loss_scale != 1.0
        )


def _mk(opt_level, **kw) -> Policy:
    return Policy(opt_level=opt_level, **kw)


O0 = _mk("O0")
O1 = _mk(
    "O1",
    compute_dtype=jnp.float16,
    keep_norm_fp32=True,
    loss_scale="dynamic",
    per_op_casts=True,
)
O2 = _mk(
    "O2",
    param_dtype=jnp.float16,
    compute_dtype=jnp.float16,
    keep_norm_fp32=True,
    master_weights=True,
    loss_scale="dynamic",
)
O3 = _mk("O3", param_dtype=jnp.float16, compute_dtype=jnp.float16)
O4 = _mk(
    "O4",
    compute_dtype=jnp.bfloat16,
    keep_norm_fp32=True,
    per_op_casts=True,
)
O5 = _mk(
    "O5",
    param_dtype=jnp.bfloat16,
    compute_dtype=jnp.bfloat16,
    keep_norm_fp32=True,
    master_weights=True,
)

opt_levels = {"O0": O0, "O1": O1, "O2": O2, "O3": O3, "O4": O4, "O5": O5}


# Reference amp.initialize kwarg names → Policy field names, so calls written
# against the reference API (frontend.py:259 signature) work unchanged.
_REFERENCE_KEY_ALIASES = {
    "keep_batchnorm_fp32": "keep_norm_fp32",
    "cast_model_type": "param_dtype",
    "patch_torch_functions": "per_op_casts",
}


def policy_for_opt_level(opt_level: Union[str, Policy], **overrides) -> Policy:
    """Look up an opt level and apply user overrides.

    Mirrors ``amp.initialize``'s override handling — explicit kwargs win over
    the opt-level preset (reference frontend.py:374-397). Reference kwarg
    names (``keep_batchnorm_fp32``, ``cast_model_type``,
    ``patch_torch_functions``) are accepted as aliases.
    """
    if isinstance(opt_level, Policy):
        policy = opt_level
    else:
        if opt_level not in opt_levels:
            raise ValueError(
                f"Unexpected optimization level {opt_level!r}; "
                "options are 'O0', 'O1', 'O2', 'O3', 'O4', 'O5'."
            )
        policy = opt_levels[opt_level]
    if overrides:
        overrides = {
            _REFERENCE_KEY_ALIASES.get(k, k): v for k, v in overrides.items()
        }
        fields = {f.name for f in dataclasses.fields(Policy)}
        unknown = set(overrides) - fields
        if unknown:
            raise ValueError(
                f"Unknown amp option(s) {sorted(unknown)}; valid options: "
                f"{sorted(fields | set(_REFERENCE_KEY_ALIASES))}"
            )
        policy = dataclasses.replace(policy, **overrides)
    return policy
