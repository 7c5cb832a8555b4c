"""Plain reference of the 2-simplex tests (arXiv:2208.11617, section 5).

Written from the paper's definitions, independently of the program: the
domain of side ``n`` is the inclusive lower triangle ``{col <= row}`` of an
``n x n`` array; an element outside it is never written, so it keeps its
value. ACCUM adds one to every domain element. CA is one step of Conway's
B3/S23 on the domain: a cell's neighbours are its eight neighbours on the
underlying square with periodic wrap, a neighbour outside the domain counts
as dead. Everything is exact int32, computed with whole-array ``jnp`` ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STRIPE = 1024

__all__ = ["initial_state", "domain", "accum_after", "ca_step", "mismatches",
           "control_accum_after", "control_ca_step"]


def domain(n: int):
    """The inclusive lower triangle of an ``n x n`` array."""
    r = jnp.arange(n)
    return r[None, :] <= r[:, None]


def initial_state(key, n: int, init: str, high: int = 0):
    """The first state: ``bits`` uniform in {0, 1}, or ``ints`` in [0, high)."""
    if init == "bits":
        return jax.random.bernoulli(key, 0.5, (n, n)).astype(jnp.int32)
    return jax.random.randint(key, (n, n), 0, high, jnp.int32)


@jax.jit
def accum_after(x0, launches):
    """The state after ``launches`` ACCUM launches from ``x0``."""
    return jnp.where(domain(x0.shape[0]), x0 + launches, x0)


@jax.jit
def ca_step(s):
    """One B3/S23 generation on the domain, periodic on the square.

    Computed a stripe of ``STRIPE`` rows at a time, so that the neighbour
    sums of a 1 GiB state need tens of MB beside it, not eight copies.
    """
    n = s.shape[0]
    b = min(STRIPE, n)
    live = jnp.where(domain(n), s, 0)
    wrapped = jnp.concatenate([live[-1:], live, live[:1]])  # periodic rows

    def stripe(i):
        blk = jax.lax.dynamic_slice_in_dim(wrapped, i * b, b + 2, 0)
        count = sum(jnp.roll(blk, dx, 1)[1 + dy:1 + dy + b]
                    for dy in (-1, 0, 1) for dx in (-1, 0, 1) if dy or dx)
        c = blk[1:1 + b]
        nxt = ((c == 0) & (count == 3)) | ((c == 1) & ((count == 2) | (count == 3)))
        rows = i * b + jnp.arange(b)
        inside = jnp.arange(n)[None, :] <= rows[:, None]
        old = jax.lax.dynamic_slice_in_dim(s, i * b, b, 0)
        return jnp.where(inside, nxt.astype(s.dtype), old)

    return jax.lax.map(stripe, jnp.arange(n // b)).reshape(n, n)


@jax.jit
def mismatches(a, b):
    """Elements at which ``a`` and ``b`` differ."""
    return jnp.sum(a != b, dtype=jnp.int32)


@jax.jit
def control_accum_after(x0, launches):
    """The control of ACCUM: ``accum_after`` computed in int16.

    int16 is the integer width below the int32 the configuration states.
    """
    low = x0.astype(jnp.int16)
    return jnp.where(domain(x0.shape[0]), low + launches.astype(jnp.int16),
                     low).astype(jnp.int32)


@jax.jit
def control_ca_step(s):
    """The control of CA: ``ca_step`` with the elements outside the domain zeroed.

    CA holds only 0 and 1, so no narrower type changes it; its control
    breaks the configuration's other guarantee, as an oracle that masks its
    whole output would.
    """
    return jnp.where(domain(s.shape[0]), ca_step(s), 0)
