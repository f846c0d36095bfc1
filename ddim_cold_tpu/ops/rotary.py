"""Rotary positions on token-major arrays: the per-lane tables, the rotation
in XLA (:func:`apply_rotary`), and :class:`Rotary`, a rotation handed on
unapplied to a launch that can turn the block it already holds
(``ops.flash_attention.selected_attention`` and ``masked_attention``). The
frequencies come from the stacks' configurations
(``models.laguna.rotary_frequencies``)."""

from __future__ import annotations

from typing import Any, NamedTuple

import jax.numpy as jnp

_PAIRINGS = ("rotate_half", "interleave")


def rotary_tables(length: int, head_dim: int, inv_freq, scale: float, *,
                  pairing: str = "rotate_half", first: int = 0,
                  heads: int = 1) -> tuple:
    """``(cos, sin)``, float32 ``(length, heads · head_dim)``, of the rotation
    of dims ``first .. first + 2 · len(inv_freq)`` of every head by token
    position 0, 1, …: ``scale · cos(t · inv_freq)`` and ``scale · sin(…)`` on
    the lanes that turn, the sine negative on the first of a pair (``[-sin,
    sin]`` by halves for ``rotate_half``, interleaved for ``interleave``), 1
    and 0 on the lanes that pass through."""
    half = len(inv_freq)
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32))  # (length, half)
    cos, sin = scale * jnp.cos(angle), scale * jnp.sin(angle)
    rest = jnp.ones((length, head_dim - first - 2 * half), jnp.float32)
    lead = [jnp.ones((length, first), jnp.float32)] if first else []
    if pairing not in _PAIRINGS:
        raise ValueError(f"pairing {pairing!r}: 'rotate_half' and "
                         "'interleave' are written")
    halves = pairing == "rotate_half"
    cos = [cos, cos] if halves else [jnp.repeat(cos, 2, axis=-1)]
    cos = jnp.tile(jnp.concatenate(lead + cos + [rest], axis=-1), (1, heads))
    sin = ([-sin, sin] if halves
           else [jnp.stack([-sin, sin], axis=-1).reshape(length, 2 * half)])
    sin = jnp.tile(jnp.concatenate([0 * t for t in lead] + sin + [0 * rest],
                                   axis=-1), (1, heads))
    return cos, sin


def apply_rotary(x, heads: int, inv_freq, scale: float, *,
                 pairing: str = "rotate_half", first: int = 0):
    """Rotate ``2 · len(inv_freq)`` dims of every head of ``x`` ``(n, L,
    heads · head_dim)``, from dim ``first`` of the head on, by token position,
    in float32; the rest pass through. ``pairing``: ``rotate_half`` (dim j
    with dim j + rot/2) or ``interleave`` (dim 2j with dim 2j + 1). Written on
    the token-major array as the projection left it — per-lane tables and two
    lane rolls, no ``(n, L, heads, head_dim)`` view — so that q and k reach the
    attention kernel in the layout it reads (a 4-d view costs a copy of the
    array on each side of the rotation).

    Every lane of ``x`` goes through float32, the ones that pass through too
    (times 1, plus 0), in passes over the whole array in HBM — the convert,
    two rolls, a select, two products, an add. Cheap on a narrow array: the
    ``laguna`` stack's k (8 K/V heads, a sixth to a ninth of q), the latent
    stacks' ``k_r`` and ``q_r`` apart, the indexer's q and k. On a wide one
    it is what :class:`Rotary` is there to avoid: the ``glm`` stack's q (64
    heads of 256, a quarter of whose columns turn) and the ``laguna`` stack's
    (48 or 72 heads of 128, every dim or the first half) reach their launch
    unturned, and this function turns them only off the TPU and for a head
    the launch cannot turn (:meth:`Rotary.apply`, its one caller with
    ``first``)."""
    n, L, W = x.shape
    hd, half = W // heads, len(inv_freq)
    cos, sin = rotary_tables(L, hd, inv_freq, scale, pairing=pairing,
                             first=first, heads=heads)
    halves = pairing == "rotate_half"
    reach = half if halves else 1
    xf = x.astype(jnp.float32)
    # the first of a pair takes its partner from the right, the partner from
    # the left
    dim = jnp.arange(W) % hd
    if first:
        dim = dim - first
    first_of_pair = dim < half if halves else dim % 2 == 0
    partner = jnp.where(first_of_pair, jnp.roll(xf, -reach, axis=-1),
                        jnp.roll(xf, reach, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


class Rotary(NamedTuple):
    """A rotation not yet applied: :func:`apply_rotary`'s arguments after
    ``heads`` (``Rotary(*rotary_frequencies(…), pairing, first)``), for a
    reader that may turn the array where it holds it."""

    inv_freq: Any
    scale: float
    pairing: str = "rotate_half"
    first: int = 0

    def apply(self, x, heads: int):
        return apply_rotary(x, heads, self.inv_freq, self.scale,
                            pairing=self.pairing, first=self.first)
