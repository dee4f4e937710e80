"""Weights made on the device, in one jitted call, from ``--seed``.

The program's layers initialise each parameter on the host with numpy
(``dygraph.layers.eager_init``), which for 1.3B parameters is 50-70 s of
every run (PERF.md). The benchmark pays set-up in every run of every
check, so while a model is built here that one function is replaced by a
recorder: each parameter gets a zero placeholder on the device and its
initializer is noted; then the placeholders are dropped and ONE jitted
program draws every normal leaf from the seed and fills every constant
one, in float32 as served. The statistics are the program's own (same mean
and std per leaf); the values differ from a host-seeded build, which no
cell depends on: correctness compares the program with the reference on
these same weights.

What only a program change can do better is listed in PERF.md: an
initializer that runs on the device would make this patch unnecessary.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def recording(sharding=None):
    """While open, every parameter the program creates is a zero
    placeholder on the device (placed by ``sharding`` if given); yields the
    record :func:`fill` needs."""
    from paddle_tpu import initializer as init
    from paddle_tpu.dygraph import layers

    real = layers.eager_init
    specs: dict = {}

    def record(initializer, shape, dtype, rng):
        if isinstance(initializer, init.NormalInitializer):
            spec = ("normal", float(initializer.loc), float(initializer.scale))
        elif isinstance(initializer, init.ConstantInitializer):
            spec = ("const", float(initializer.value), 0.0)
        else:
            raise TypeError(
                f"perfbench.weights: no device form of {type(initializer)}")
        value = jnp.zeros(tuple(int(d) for d in shape), dtype,
                          device=sharding)
        specs[id(value)] = spec
        return value

    layers.eager_init = record
    try:
        yield specs
    finally:
        layers.eager_init = real


def seed_key(seed: int):
    """A PRNG key from ``--seed``; a seed may exceed 32 signed bits, so it
    is folded in as two halves."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def fill(model, specs: dict, seed: int, sharding=None):
    """Draw every parameter of ``model`` (built under :func:`recording`)
    on the device from ``seed``, in one jitted call. ``sharding`` is the
    one the placeholders were recorded with (a mesh's replicated sharding
    for a step over a mesh: the step then finds its state where it wants
    it)."""
    params = [p for _, p in model.named_parameters()]
    leaves = []
    for p in params:
        if id(p.value) not in specs:
            raise RuntimeError(
                "perfbench.weights: a parameter was not made through "
                "eager_init; its initializer is unknown")
        leaves.append((p.value.shape, p.value.dtype, *specs[id(p.value)]))
        # the placeholder goes before the draw: donated, it was still held
        # beside the new weights and doubled the peak of device memory
        p.value = None

    def draw(key):
        out = []
        for i, (shape, dtype, kind, a, b) in enumerate(leaves):
            if kind == "const":
                out.append(jnp.full(shape, a, dtype))
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
                out.append(a + b * z)
        return out

    new = jax.jit(draw, out_shardings=sharding)(seed_key(seed))
    for p, v in zip(params, new):
        p.value = v
