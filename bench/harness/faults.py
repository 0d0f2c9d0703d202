"""Faults planted underneath the timed path, to show the check catches them.

Each fault is a context manager that patches the program for the span of
a run (the program's files are untouched):

* ``state_unchanged``: the optimizer returns the parameters and its state
  as it got them, so the step leaves the state unchanged;
* ``update_sign_flipped``: the optimizer's update is applied the wrong
  way (its state, and so the gradient it read, stay right);
* ``half_batch``: the loss is taken over the first half of each worker's
  rows only, the mean over those;
* ``no_exchange``: the packed sign vote leaves out the exchange between
  chips: each worker decodes its own vote, with no all-to-all and no
  all-gather.
"""
from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def _optimizer_apply(wrap):
    """Every step built meanwhile runs its optimizer's ``apply`` as
    ``wrap(apply, params, grads, state)``."""
    from repro.fabric import session

    orig = session.Fabric.build_step

    def build_step(self, cfg, optimizer, *args, **kwargs):
        base = type(optimizer)

        @dataclasses.dataclass(frozen=True)
        class Wrapped(base):
            def apply(self, params, grads, state):
                return wrap(super().apply, params, grads, state)

        wrapped = Wrapped(**{f.name: getattr(optimizer, f.name)
                             for f in dataclasses.fields(optimizer)})
        return orig(self, cfg, wrapped, *args, **kwargs)

    with _patched(session.Fabric, "build_step", build_step):
        yield


def state_unchanged():
    return _optimizer_apply(lambda apply, params, grads, state:
                            (params, state))


def update_sign_flipped():
    import jax
    import jax.numpy as jnp

    def flipped(apply, params, grads, state):
        new, state = apply(params, grads, state)
        back = lambda p, n: (2 * p.astype(jnp.float32)
                             - n.astype(jnp.float32)).astype(p.dtype)
        return jax.tree.map(back, params, new), state

    return _optimizer_apply(flipped)


@contextlib.contextmanager
def half_batch():
    import repro.models as models

    orig = models.loss_fn

    def loss_fn(params, cfg, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return orig(params, cfg, half)

    with _patched(models, "loss_fn", loss_fn):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.core import lowbit

    orig = lowbit._packed_a2a_local

    def alone(g, dp_axes, num_workers, **kwargs):
        # no data-parallel axis: the one-worker vote, on this worker's signs
        return orig(g, (), 1, **kwargs)

    with _patched(lowbit, "_packed_a2a_local", alone):
        yield


FAULTS = {"state_unchanged": state_unchanged,
          "update_sign_flipped": update_sign_flipped,
          "half_batch": half_batch,
          "no_exchange": no_exchange}

