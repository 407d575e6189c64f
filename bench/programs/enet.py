"""How the benchmark calls the system under test for ENet: the model's
forward and the repository's train recipe, on the Pallas engines
(compiled on a TPU, interpreted on a CPU where only the tests run).  The
weights come from the benchmark (``bench/refs/enet.py``)."""

from __future__ import annotations

import functools


def forward(cfg: dict):
    """``f(params, x) -> logits``: the entry a segmentation user calls."""
    from repro.models import enet

    return functools.partial(enet.forward, backend="pallas", interpret=None)


def train(cfg: dict):
    """``(init_state(params), step(state, batch) -> (state, metrics))``."""
    from repro.launch import train_recipes

    opt = cfg["optimizer"]
    step = train_recipes.make_train_step(
        "enet", backend="pallas", interpret=None, lr=opt["lr"],
        weight_decay=opt["weight_decay"])
    return train_recipes.init_state, step


def params_of(state) -> dict:
    return state.params


def first_moment(state) -> dict:
    """AdamW's first moment: ``(1 - b1) * g`` after the first step."""
    return state.opt.mu
