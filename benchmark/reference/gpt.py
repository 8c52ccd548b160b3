"""Plain float32 GPT-2 (learned positions, pre-LN blocks, tied head) with
its next-token loss, after the published model.  Imports nothing from
apex_tpu.  The configuration is the model's own ``config.json`` keys."""

from __future__ import annotations

from . import transformer as T

INIT_STD = 0.02


def param_spec(cfg: dict) -> dict:
    h, L = cfg["n_embd"], cfg["n_layer"]
    ffn = cfg.get("n_inner") or 4 * h
    return {
        "embedding": {
            "word": ((cfg["vocab_size"], h), 0.0, INIT_STD),
            "position": ((cfg["n_positions"], h), 0.0, INIT_STD),
        },
        "layers": T.layer_spec(L, h, ffn, INIT_STD),
        "final_ln": {"scale": ((h,), 1.0, INIT_STD),
                     "bias": ((h,), 0.0, INIT_STD)},
    }


def init_params(key, cfg: dict) -> dict:
    return T.normal_tree(key, param_spec(cfg))


def loss(params, batch, cfg: dict, prec: T.Precision):
    """``batch`` = (tokens [b, s], labels [b, s]); labels of -1 are left
    out of the mean.  The caller has already shifted the labels."""
    tokens, labels = batch
    emb = params["embedding"]
    s = tokens.shape[1]
    x = emb["word"][tokens] + emb["position"][:s][None]
    x = T.stack(x, params["layers"], n_heads=cfg["n_head"], causal=True,
                pre_ln=True, eps=cfg["layer_norm_epsilon"],
                act=cfg["activation_function"], prec=prec)
    fl = params["final_ln"]
    x = T.layer_norm(x, fl["scale"], fl["bias"], cfg["layer_norm_epsilon"])
    total, count = T.blocked_cross_entropy(
        x.reshape(-1, x.shape[-1]), emb["word"], 0.0, labels.reshape(-1),
        prec)
    return total / count
