"""Faults planted under the timed path, to show that the comparison fails
them. Each is a function ``plant(trainer)`` that breaks the trainer's step
in place; ``no_exchange`` also returns an undo (it patches the program's
gradient sync for the process).

- ``unchanged``: the step returns the state it was given, unchanged,
  with the batch's loss (a forward pass of the program's loss);
- ``half_batch``: the second half of each chip's rows is left out of
  the loss (their labels masked, which the program's loss skips), so the
  mean is taken over the rest; the shapes, and so the compiled programs,
  stay those of the timed path;
- ``no_exchange``: the gradient buckets' allreduce is left out, so each
  chip applies its own gradient (over the chip count).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import numpy as np


def unchanged(tm) -> None:
    from repro.train.step import loss_fn
    loss = jax.jit(lambda p, b: loss_fn(p, b, tm.mcfg, tm.tcfg)[0])
    tm.step = lambda p, o, b: (p, o, {"loss": loss(p, b)})


def half_batch(tm) -> None:
    real = tm.put
    per_chip = int(tm.traffic["batch_per_chip"])

    def put(batch):
        labels = np.array(batch["labels"])
        rows = np.arange(labels.shape[0]) % per_chip >= per_chip // 2
        labels[rows] = -1
        return real(dict(batch, labels=labels))

    tm.put = put


def no_exchange(tm) -> Callable[[], None]:
    from repro.train import manual_step
    cls = manual_step.OverlappedGradSync
    saved = cls.start, cls.wait
    cls.start = lambda self, i, payload: payload
    cls.wait = lambda self, i, handle, block=False: handle

    def undo():
        cls.start, cls.wait = saved

    return undo


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}


def plant(name: str, tm) -> Optional[Callable[[], None]]:
    return FAULTS[name](tm)
