"""The reference's first training steps and the comparison that decides
``correct`` for a training cell.  Imports nothing from apex_tpu.

``first_steps`` drives a plain float32 loop (loss, gradients, optimizer) over
the same batches the timed path took its first steps on, and returns the
readings that are compared: each step's loss, every leaf's first moment
after one step (the gradient as the optimizer got it, up to the optimizer's
own constant), and every leaf's change after the last step.  ``precision``
below float32 makes it a control; ``fault`` plants one of the faults a
training step can have, for the tests and for reading the limits on the
chip.  ``reference_steps`` is what a run compares with: the float32
readings and the unit that ``grad_noise`` is measured in.
"""

from __future__ import annotations

import importlib
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from . import optim
from . import transformer as T

N_STEPS = 3
FAULTS = ("state_unchanged", "half_batch")
# A leaf whose first gradient is nought to rounding (a key's bias under
# softmax) moves under Adam by round-off alone: such leaves, by this rule
# on the REFERENCE's gradient, are left out of the change comparison.
ZERO_GRAD_SHARE = 1e-3


def model_module(name: str):
    return importlib.import_module(f"{__package__}.{name}")


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def tree_difference(new, old):
    return jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new, old)


@jax.jit
def moved_mask(moment):
    """Per leaf, which elements count in the change comparison: those whose
    gradient (the first moment after one step) is not nought, that is at
    least ZERO_GRAD_SHARE of the root-mean-square gradient of their own
    leaf or of the median leaf, whichever is larger.  Called on the
    REFERENCE's moment."""
    leaves, tree = jax.tree_util.tree_flatten(moment)
    rms = jnp.stack([_norm(m) / jnp.sqrt(float(m.size)) for m in leaves])
    floor = ZERO_GRAD_SHARE * jnp.maximum(rms, jnp.median(rms))
    return jax.tree_util.tree_unflatten(
        tree, [jnp.abs(m) >= f for m, f in zip(leaves, floor)])


@jax.jit
def _moved_norms(delta, moved):
    return [_norm(jnp.where(k, d, 0.0)) for d, k in zip(
        jax.tree_util.tree_leaves(delta), jax.tree_util.tree_leaves(moved))]


@jax.jit
def _moment_norms(got, want):
    """Per leaf: each side's norm, and the norm of their difference."""
    pairs = list(zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)))
    return ([_norm(g) for g, _ in pairs], [_norm(w) for _, w in pairs],
            [_norm(g.astype(jnp.float32) - w.astype(jnp.float32))
             for g, w in pairs])


def first_steps(ref: dict, cfg: dict, make_params, batches, *,
                precision: str = "float32", fault: str = "",
                n_steps: int = N_STEPS) -> dict:
    """``ref`` is the configuration's ``reference`` block (``model``,
    ``optimizer``, ``optimizer_kwargs``); ``make_params()`` gives the
    initial weights anew each time it is called (so that no copy of them
    lives on the device through the steps); ``batches`` are the first
    N_STEPS host batches."""
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    model = model_module(ref["model"])
    prec = T.Precision(precision)
    opt_init, opt_update = optim.OPTIMIZERS[ref["optimizer"]](
        **ref["optimizer_kwargs"])

    def step(params, opt_state, batch):
        if fault == "half_batch":
            half = jax.tree_util.tree_leaves(batch)[0].shape[0] // 2
            batch = jax.tree_util.tree_map(lambda x: x[:half], batch)
        loss, grads = jax.value_and_grad(model.loss)(
            params, batch, cfg, prec)
        new_params, new_opt = opt_update(grads, opt_state, params)
        if fault == "state_unchanged":
            new_params, new_opt = params, opt_state
        return new_params, new_opt, loss

    jstep = jax.jit(step, donate_argnums=(0, 1))
    params = make_params()
    opt_state = opt_init(params)
    losses = []
    for i in range(n_steps):
        params, opt_state, loss = jstep(params, opt_state, batches[i])
        losses.append(loss)
        if i == 0:
            # a copy: the next step's donation takes the state's own
            moment = jax.tree_util.tree_map(jnp.copy, opt_state["m"])
            moved = moved_mask(moment)
    del opt_state
    out = readings(losses, moment, tree_difference(params, make_params()))
    out["moved"] = moved
    return out


def reference_steps(ref: dict, cfg: dict, make_params, batches) -> dict:
    """The float32 reference's readings, and ``noise``: per leaf, how far
    the reference's own first moment moves when its products round their
    operands and cotangents to bfloat16, the precision the configurations
    state.  That is the unit of ``grad_noise``.  A seed's batch and
    weights decide how much of the gradient cancels between rows (BERT's
    next-sentence term, 8 rows, swings the gradient's norm threefold from
    seed to seed while the rounding error stays where it is), so an error
    measured against the gradient's norm swings with it; measured against
    this unit it does not."""
    rounded = first_steps(ref, cfg, make_params, batches,
                          precision="bfloat16", n_steps=1)["moment"]
    want = first_steps(ref, cfg, make_params, batches)
    want["noise"] = _floats(_moment_norms(rounded, want["moment"])[2])
    return want


def readings(losses, moment, delta) -> dict:
    """One side's readings: each step's loss, ``moment``, the tree of the
    optimizer's first moment after one step, and ``delta``, the tree of
    the parameters' change after the last step.  Both trees are left where
    they are (device or host)."""
    return {"names": leaf_names(delta),
            "loss": [float(x) for x in jax.device_get(losses)],
            "moment": moment,
            "delta": delta}


def leaf_gaps(got, want) -> list:
    """Per leaf, the gap between two norms of it, against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but nought)."""
    floor = statistics.median(want)
    gaps = [abs(g - w) / max(w, floor) for g, w in zip(got, want)]
    return [g if np.isfinite(g) else float("inf") for g in gaps]


def _floats(xs) -> list:
    return [float(x) for x in jax.device_get(xs)]


def compare(got: dict, want: dict, detail: bool = False) -> dict:
    """The numbers compared, each a gap of ``got`` (the timed path, or the
    control in its place) from ``want`` (``reference_steps``, which brings
    ``moved``, the elements that count in the change, and ``noise``).

    ``grad_norm`` and ``param_change`` are gaps between the two sides'
    norms of a leaf: second order in an unbiased rounding error, so they
    show a leaf that did not move or moved double (it reads 1 there) and
    rows left out, but not a lower precision whose rounding is fair.  Of
    the leaves' gaps both the worst and the median are given: the worst
    leaf swings from seed to seed with the smallest leaves' noise, the
    median leaf is steady.

    ``grad_noise`` is the norm of the two sides' difference of the first
    moment, all leaves as one vector, in units of ``noise``: first order
    in any rounding error, and the number a lower precision moves."""
    if got["names"] != want["names"]:
        raise ValueError("the two sides do not hold the same leaves")
    out = {}
    for i, (g, w) in enumerate(zip(got["loss"], want["loss"])):
        gap = abs(g - w) / abs(w)
        out[f"loss{i + 1}"] = gap if np.isfinite(gap) else float("inf")
    got_m, want_m, diff_m = map(
        _floats, _moment_norms(got["moment"], want["moment"]))
    changes = [_floats(_moved_norms(side["delta"], want["moved"]))
               for side in (got, want)]
    per_leaf = {
        "grad_norm": leaf_gaps(got_m, want_m),
        "param_change": leaf_gaps(*changes),
    }
    for name, gaps in per_leaf.items():
        out[name] = max(gaps)
        out[name + "_median"] = statistics.median(gaps)
    gap = math.hypot(*diff_m) / math.hypot(*want["noise"])
    out["grad_noise"] = gap if np.isfinite(gap) else float("inf")
    if detail:
        out["leaves"] = {"names": want["names"], **per_leaf,
                         "moment_norm": want_m, "moment_diff": diff_m,
                         "noise": want["noise"]}
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``: every number that has a
    limit must lie at or under it; a number with no limit is shown with
    ``limit`` null and not judged."""
    missing = set(limits) - set(numbers)
    if missing:
        raise ValueError(f"limits for numbers never read: {sorted(missing)}")
    shown = {name: {"value": value, "limit": limits.get(name)}
             for name, value in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in shown.values()
                  if v["limit"] is not None)
    return correct, shown
