"""The one general generator of a training cell's batches.  A traffic mix is
a data file (``benchmark/workloads/<cell>.json``): batch, sequence length,
how many distinct host batches are cycled (``pool``), and an ordered list of
``inputs``, the positional arguments the step takes after its state.  Every
draw comes from ``numpy.random.default_rng(seed)``, so a seed fixes the
batches, and every seed gives the same sizes.

Kinds of input:

- ``token_window``: columns ``offset .. offset+seq`` of one ``[batch,
  seq+span]`` draw of uniform ids shared by all windows of the batch (a
  decoder's tokens and its next-token labels are two windows of one draw);
- ``uniform_ids``: uniform ids under ``high`` (default: the vocabulary), of
  shape ``[batch, seq]`` or, with ``per`` ``row``, ``[batch]``;
- ``masked_ids``: uniform ids at a ``share`` of the positions and
  ``ignore`` elsewhere (masked-LM labels);
- ``constant``: ``value`` everywhere (token types, an attention mask with
  no padding).
"""

from __future__ import annotations

import numpy as np


def make_pool(cell: dict, vocab: int, seed: int) -> list:
    """``pool`` batches, each a tuple of int32 arrays in ``inputs`` order."""
    rng = np.random.default_rng(seed)
    return [_one_batch(cell, vocab, rng) for _ in range(cell["pool"])]


def _one_batch(cell: dict, vocab: int, rng) -> tuple:
    b, s = cell["batch"], cell["seq"]
    span = max((spec.get("offset", 0) for spec in cell["inputs"]
                if spec["kind"] == "token_window"), default=0)
    draw = None
    out = []
    for spec in cell["inputs"]:
        kind = spec["kind"]
        shape = (b,) if spec.get("per") == "row" else (b, s)
        if kind == "token_window":
            if draw is None:
                draw = rng.integers(0, vocab, (b, s + span))
            arr = draw[:, spec.get("offset", 0):spec.get("offset", 0) + s]
        elif kind == "uniform_ids":
            arr = rng.integers(0, spec.get("high", vocab), shape)
        elif kind == "masked_ids":
            arr = np.where(rng.random(shape) < spec["share"],
                           rng.integers(0, vocab, shape), spec["ignore"])
        elif kind == "constant":
            arr = np.full(shape, spec["value"])
        else:
            raise ValueError(f"unknown input kind {kind!r}")
        out.append(np.ascontiguousarray(arr, dtype=np.int32))
    return tuple(out)
