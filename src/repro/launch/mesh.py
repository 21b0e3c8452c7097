"""Production mesh factory.

Single pod: 256 TPU v5e chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the ``pod``
axis crosses DCI and carries only data-parallel (optionally PowerSGD-
compressed) gradient traffic.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the compiler propagates
    shardings and ``with_sharding_constraint`` hints them (``Explicit``
    axes, the default since jax 0.9, reject such hints)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh over however many (fake) devices exist — used by tests."""
    n = len(jax.devices())
    if multi_pod:
        pod = 2 if n % 2 == 0 and n >= 2 else 1
        rest = n // pod
        data = _largest_factor(rest)
        return make_mesh((pod, data, rest // data),
                         ("pod", "data", "model"))
    data = _largest_factor(n)
    return make_mesh((data, n // data), ("data", "model"))


def _largest_factor(n: int) -> int:
    f = int(n ** 0.5)
    while n % f:
        f -= 1
    return max(f, 1)
